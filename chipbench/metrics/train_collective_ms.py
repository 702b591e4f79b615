"""Device time of collective operations (the ``collectives`` layer's trace
names) per step, in ms, on the chip that spends the most on them."""


def read(ctx):
    tr, steps = ctx["trace"], ctx["window"]["steps"]
    pats = ctx["layers"]["collectives"]
    per_chip = [sum(tr.op_seconds(d, pats).values()) for d in tr.ops]
    if not steps or not any(per_chip):
        return None
    return 1e3 * max(per_chip) / steps
