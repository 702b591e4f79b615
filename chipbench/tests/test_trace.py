"""The trace reduction: on synthetic events, and on a trace recorded on a
TPU v5e 2x2 host (one traced second of ``paper-40k.4chip``, committed)."""
import json
import pathlib

import pytest

import devtrace
import run
from conftest import BENCH

RECORDED = pathlib.Path(__file__).parent / "data" / "paper-40k.4chip.xplane.pb"


def test_idle_gaps_and_containers():
    ops = {0: [("while.1", 0, 100),          # a scan holding the others
               ("fusion.1", 10, 30), ("fusion.2", 30, 50),
               ("all-reduce.1", 60, 70), ("fusion.1", 90, 95)]}
    spans = [("window", 0, 100), ("fetch_metrics", 50, 90),
             ("dispatch_chunk", 95, 100)]
    tr = devtrace.Trace(ops, spans, (0, 100))
    assert [n for n, _s, _e in tr.ops[0]] == [
        "fusion.1", "fusion.2", "all-reduce.1", "fusion.1"]
    assert tr.busy_s(0) == pytest.approx(55e-9)
    assert tr.idle_gaps(0) == [(0, 10), (50, 60), (70, 90), (95, 100)]
    assert tr.op_seconds(0, ["all-reduce"]) == {"all-reduce.1": 10e-9}
    b = tr.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(25e-9)]
    assert b["idle_gaps"][0] == ["fetch_metrics", pytest.approx(20e-9)]


@pytest.fixture(scope="module")
def recorded():
    if not RECORDED.exists():
        pytest.skip("no recorded trace")
    return devtrace.Trace.from_file(str(RECORDED))


def test_recorded_trace(recorded):
    tr = recorded
    assert sorted(tr.ops) == [0, 1, 2, 3]
    assert 0.5 < tr.window_s < 5
    for d in tr.ops:
        assert 0 < tr.busy_s(d) <= tr.window_s
        assert not any(" = " in n or n.startswith("while")
                       for n, _s, _e in tr.ops[d])
    b = tr.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(n in devtrace.HOST_SPANS + ("no_span",)
               for n, _s in b["idle_gaps"])


def test_readers_on_recorded_trace(recorded):
    ctx = {"trace": recorded, "chips": 4,
           "window": {"steps": 8, "input_wait_s": 0.0},
           "layers": run.layer_patterns(BENCH), "flops_per_step": 1.0,
           "peak": json.loads((BENCH / "peaks.json").read_text())[
               "devices"]["TPU v5 lite"]}
    idle = run.load_reader(BENCH, "train_idle_share")(ctx)
    assert 0 <= idle < 100
    coll = run.load_reader(BENCH, "train_collective_ms")(ctx)
    assert coll is not None and coll > 0
    mfu = run.load_reader(BENCH, "train_mfu")(ctx)
    assert 0 < mfu < 100
