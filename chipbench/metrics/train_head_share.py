"""Share of the chip's busy time in the window spent in the loss head's
Pallas kernels (the ``kernel_head`` layer's trace names, ``loss_head*``:
``loss_head_fwd``/``_bwd``, ``loss_head_many_fwd``/``_bwd``), in %, on the
chip that spends the most on them.
Nothing to read where the kernels are unnamed or off the path."""


def read(ctx):
    tr = ctx["trace"]
    pats = ctx["layers"]["kernel_head"]
    shares = [100.0 * sum(tr.op_seconds(d, pats).values()) / tr.busy_s(d)
              for d in tr.ops if tr.busy_s(d) > 0]
    if not any(shares):
        return None
    return max(shares)
