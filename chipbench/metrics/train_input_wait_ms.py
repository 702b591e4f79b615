"""Host time the loop waited for the data plane's next slab
(``Prefetcher.get``, the harness's ``input_wait`` span), per step, in ms."""


def read(ctx):
    w = ctx["window"]
    if not w["steps"]:
        return None
    return 1e3 * w["input_wait_s"] / w["steps"]
