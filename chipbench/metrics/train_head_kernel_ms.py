"""Device time of the loss head's Pallas kernels (the ``kernel_head``
layer's trace names: ``loss_head_fwd``, ``loss_head_bwd``) per step, in
ms, on the chip that spends the most on them.  Nothing to read where the
kernels are unnamed or off the path."""


def read(ctx):
    tr, steps = ctx["trace"], ctx["window"]["steps"]
    pats = ctx["layers"]["kernel_head"]
    per_chip = [sum(tr.op_seconds(d, pats).values()) for d in tr.ops]
    if not steps or not any(per_chip):
        return None
    return 1e3 * max(per_chip) / steps
