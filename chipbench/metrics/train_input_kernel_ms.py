"""Device time of the input layer's Pallas kernels (the ``kernel_input``
layer's trace names: ``fused_input_fwd``, ``fused_input_bwd``) per step, in
ms, on the chip that spends the most on them.  Nothing to read where the
kernels are unnamed or off the path."""


def read(ctx):
    tr, steps = ctx["trace"], ctx["window"]["steps"]
    pats = ctx["layers"]["kernel_input"]
    per_chip = [sum(tr.op_seconds(d, pats).values()) for d in tr.ops]
    if not steps or not any(per_chip):
        return None
    return 1e3 * max(per_chip) / steps
