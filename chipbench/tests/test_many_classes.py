"""Tiny many-class cells on the CPU (kernels in interpret mode): 20 classes
through the fused loss head and through the XLA head.  Sound runs come out
correct, the control and the half-batch fault do not, and
``train_head_share`` reads the head's share of the chip's busy time from a
traced run.
"""
import json
import pathlib

import jax
import pytest

import calibrate
import cell as cellmod
import devtrace
import run
from conftest import BENCH, TINY, TINY_LIMITS, TINY_TRAFFIC, run_cell

RECORDED = (pathlib.Path(__file__).parent / "data"
            / "helena-10k.b256.xplane.pb")
MANY = dict(TINY, name="tinymany", classes=20)
CELLS = {"tinymany.t16": MANY,
         "tinymanyfused.t16": dict(MANY, name="tinymanyfused",
                                   bd_impl="fused")}


@pytest.fixture
def tree(tiny_tree):
    root, _bench, add = tiny_tree
    for name, cfg in CELLS.items():
        add(name, cfg, TINY_TRAFFIC, TINY_LIMITS)
    return root


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("seed", [4, 2 ** 31 + 11])
def test_sound_run_is_correct(tree, name, seed):
    res = run_cell(tree, name, seed=seed)
    assert res["correct"], res["checks"]
    assert res["checks"]["chunk_executables"]["value"] == 1


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_is_not_correct(tree, name):
    """The reference with every matmul operand rounded to 16 bits, in the
    program's place, fails one of the cell's numbers on every seed."""
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    spec = cellmod.load(tree / "chipbench", bench, name)
    with jax.default_matmul_precision("highest"):
        c = cellmod.Cell(spec, jax.devices()[:1])
        for seed in (1, 2, 3):
            with jax.set_mesh(c.mesh):
                c.setup(seed)
                c.free()
            ctrl = c.numbers(c.check(), calibrate.as_program(
                c.check(precision="bf16x2")))
            assert any(ctrl[k] > v for k, v in spec["limits"].items()), ctrl


def _half_batch(real, _cell):
    def chunk(params, state, xs, ys, lr):
        h = xs.shape[1] // 2
        return real(params, state, xs[:, :h], ys[:, :h], lr)
    return chunk


@pytest.mark.parametrize("name", sorted(CELLS))
def test_half_batch_is_not_correct(tree, name):
    res = run_cell(tree, name, wrap=_half_batch)
    assert not res["correct"], res["checks"]


def test_head_share_on_a_traced_cpu_run(tree):
    """Listed for the fused cell, the reader finds no chip in a CPU trace
    and the line leaves the metric out, without failing the run."""
    meta = json.loads((tree / "BENCHMARK.json").read_text())
    for m in meta["per_layer"]:
        if m["name"] == "train_head_share":
            m["workloads"].append("tinymanyfused.t16")
    (tree / "BENCHMARK.json").write_text(json.dumps(meta))
    res = run_cell(tree, "tinymanyfused.t16", trace=1)
    assert res["correct"], res["checks"]
    assert "train_input_wait_ms" in res["metrics"]
    assert "train_head_share" not in res["metrics"]


def _share(trace):
    return run.load_reader(BENCH, "train_head_share")(
        {"trace": trace, "layers": run.layer_patterns(BENCH),
         "window": {"steps": 8, "input_wait_s": 0.0}})


def test_head_share_on_synthetic_events():
    """The head's kernels over the busy time, on the chip where that share
    is largest; idle time and other operations do not count as head."""
    ops = {0: [("jvp_loss_head_fwd_.8", 0, 2), ("fusion.1", 2, 6),
               ("transpose_jvp_loss_head_bwd__.8", 6, 8)],
           1: [("loss_head_fwd.1", 0, 3), ("fusion.2", 3, 4)]}
    tr = devtrace.Trace(ops, [("window", 0, 20)], (0, 20))
    assert _share(tr) == pytest.approx(75.0)
    none = devtrace.Trace({0: [("fusion.1", 0, 5)]}, [("window", 0, 5)],
                          (0, 5))
    assert _share(none) is None


def test_head_share_on_recorded_trace():
    """One traced second of ``helena-10k.b256`` on a TPU v5e, committed:
    the head's kernels take a share of the busy time between 0 and 100."""
    v = _share(devtrace.Trace.from_file(str(RECORDED)))
    assert 0 < v < 100
