"""Share of the traced window in which no operation ran on the chip, in %:
1 - (union of the device-operation intervals) / window, on the idlest chip."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.ops:
        return None
    return max(100.0 * (1.0 - tr.busy_s(d) / tr.window_s) for d in tr.ops)
