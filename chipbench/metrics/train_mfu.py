"""Model FLOP utilisation of the whole training step, in %: the operations
the forward and backward passes of the real members require (``flops.py``)
times the steps of the traced window, over the window, the chips and each
chip's bf16 peak (``peaks.json``)."""


def read(ctx):
    tr, peak = ctx["trace"], ctx["peak"]
    if peak is None or not ctx["window"]["steps"]:
        return None
    work = ctx["flops_per_step"] * ctx["window"]["steps"]
    return 100.0 * work / (tr.window_s * ctx["chips"]
                           * peak["bf16_flops_per_s"])
