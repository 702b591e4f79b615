"""What the program traces of itself: the per-kernel readers on synthetic
device events and on the recorded trace, and the data plane's spans in a
traced tiny cell on the CPU."""
import pathlib

import pytest

import devtrace
import run
from conftest import BENCH, TINY, TINY_LIMITS, TINY_TRAFFIC, run_cell

RECORDED = pathlib.Path(__file__).parent / "data" / "paper-40k.4chip.xplane.pb"
KERNEL_READERS = {"train_input_kernel_ms": "fused_input",
                  "train_mid_kernel_ms": "fused_mid",
                  "train_head_kernel_ms": "loss_head"}


def _ctx(trace, steps=4):
    return {"trace": trace, "chips": len(trace.ops),
            "window": {"steps": steps, "input_wait_s": 0.0},
            "layers": run.layer_patterns(BENCH), "flops_per_step": 1.0,
            "peak": None}


def test_kernel_layers_name_the_training_kernels():
    layers = run.layer_patterns(BENCH)
    assert {k: layers[k] for k in ("kernel_input", "kernel_mid",
                                   "kernel_head")} == {
        "kernel_input": ["fused_input"], "kernel_mid": ["fused_mid"],
        "kernel_head": ["loss_head"]}


def test_kernel_readers_on_synthetic_events():
    """Each reader sums its layer's kernels, forward and backward, on the
    chip that spends the most on them, per step; other operations and the
    other layers' kernels do not count."""
    ms = 1_000_000
    ops = {0: [("jvp_fused_input_fwd_.8", 0, 4 * ms),
               ("jvp_fused_mid_fwd_.16", 4 * ms, 10 * ms),
               ("jvp_loss_head_fwd_.8", 10 * ms, 12 * ms),
               ("transpose_jvp_loss_head_bwd__.8", 12 * ms, 15 * ms),
               ("transpose_jvp_fused_mid_bwd__.16", 15 * ms, 23 * ms),
               ("transpose_jvp_fused_input_bwd__.8", 23 * ms, 28 * ms),
               ("fusion.3", 28 * ms, 40 * ms)],
           1: [("fused_input_fwd.1", 0, 2 * ms),
               ("loss_head_fwd.1", 2 * ms, 9 * ms)]}
    tr = devtrace.Trace(ops, [("window", 0, 40 * ms)], (0, 40 * ms))
    got = {m: run.load_reader(BENCH, m)(_ctx(tr)) for m in KERNEL_READERS}
    assert got == pytest.approx({"train_input_kernel_ms": 9 / 4,
                                 "train_mid_kernel_ms": 14 / 4,
                                 "train_head_kernel_ms": 7 / 4})


def test_kernel_readers_find_nothing_where_kernels_are_unnamed():
    """A trace whose kernels carry no name (``jvp__.33``) or that runs no
    kernel (the XLA path) gives no reading, rather than a zero."""
    tr = devtrace.Trace({0: [("jvp__.33", 0, 10), ("fusion.1", 10, 20)]},
                        [("window", 0, 20)], (0, 20))
    for m in KERNEL_READERS:
        assert run.load_reader(BENCH, m)(_ctx(tr)) is None


def test_kernel_readers_on_recorded_trace():
    """The committed trace predates the kernels' names: its Pallas calls
    read ``shard_map.N``, so no kernel reader finds anything there."""
    if not RECORDED.exists():
        pytest.skip("no recorded trace")
    tr = devtrace.Trace.from_file(str(RECORDED))
    assert any(n.startswith("shard_map.") for n, _s, _e in tr.ops[0])
    for m in KERNEL_READERS:
        assert run.load_reader(BENCH, m)(_ctx(tr, steps=8)) is None


def _host_spans(trace_dir) -> list:
    """``(name, thread, start_ns, end_ns)`` of the host events of the one
    trace under ``trace_dir``; ``thread`` is the event's line index."""
    from jax.profiler import ProfileData
    path, = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    return [(e.name, i, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for i, line in enumerate(plane.lines) for e in line.events]


def test_traced_cell_holds_the_program_spans(tiny_tree, tmp_path):
    """In a traced run the producer's ``prefetch.build`` spans lie on a
    thread other than the loop's, and each metric fetch's
    ``metrics.resolve`` lies inside the harness's ``fetch_metrics`` (or,
    for the last chunk, ``drain``) on the loop's thread."""
    root, _bench, add = tiny_tree
    add("tiny.t16", TINY, TINY_TRAFFIC, TINY_LIMITS)
    res = run_cell(root, "tiny.t16", trace=1, trace_dir=str(tmp_path))
    assert res["correct"], res["checks"]
    # no device plane on the CPU: the kernel readers find nothing to read
    assert not set(KERNEL_READERS) & set(res["metrics"])
    spans = _host_spans(tmp_path)
    (_, loop, w0, w1), = [s for s in spans if s[0] == "window"]
    builds = [s for s in spans if s[0] == "prefetch.build"]
    assert builds and all(t != loop for _n, t, _s, _e in builds)
    outer = [s for s in spans if s[0] in ("fetch_metrics", "drain")]
    resolves = [s for s in spans if s[0] == "metrics.resolve"]
    assert len(resolves) == res["attempted"] + 1   # chunk 0's, then each
    for _n, t, s, e in resolves:
        assert t == loop and w0 <= s <= e <= w1
        assert any(o[1] == t and o[2] <= s and e <= o[3] for o in outer)
    assert any(o[0] == "fetch_metrics" and o[2] <= resolves[0][2]
               for o in outer)
