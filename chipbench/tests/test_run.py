"""Whole runs of tiny cells on the CPU (kernels in interpret mode): sound
runs come out correct; the control and every fault a training cell can have
come out not correct; and a configuration, traffic mix, cell, per-layer
metric and layer's trace names are each added as a file of their own.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import calibrate
import cell as cellmod
from conftest import TINY, TINY_LIMITS, TINY_TRAFFIC, run_cell

TINY_DEEP = dict(
    TINY, name="tinydeep", bd_impl="fused",
    population={"kind": "list", "widths": [[9, 5], [7, 3, 2], [4]],
                "activations": ["relu", "tanh", "elu", "mish"],
                "repeats": 3},
    optimizer={"name": "adamw", "lr": 0.01, "b1": 0.9, "b2": 0.95,
               "eps": 1e-8, "weight_decay": 0.0})
TINY_SHARDED = dict(TINY, name="tinyfused", bd_impl="fused",
                    population=dict(TINY["population"], repeats=4))
CELLS = {"tiny.t16": (TINY, 1), "tinydeep.t16": (TINY_DEEP, 1),
         "tinyfused.4chip": (TINY_SHARDED, 4)}


@pytest.fixture
def tree(tiny_tree):
    root, _bench, add = tiny_tree
    for name, (cfg, chips) in CELLS.items():
        limits = dict(TINY_LIMITS)
        if cfg["optimizer"]["name"] == "adamw":
            limits["moment"] = TINY_LIMITS["change"]
        add(name, cfg, TINY_TRAFFIC, limits, chips=chips)
    return root


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("seed", [1, 2 ** 33 + 5])
def test_sound_run_is_correct(tree, name, seed):
    res = run_cell(tree, name, seed=seed, chips=CELLS[name][1])
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["checks"]["window_compiles"]["value"] == 0
    # the checked first chunk and the window ran one executable
    assert res["checks"]["chunk_executables"]["value"] == 1
    m = res["metrics"]
    assert set(m) == {"train_member_steps_per_s", "setup_s"}
    assert m["train_member_steps_per_s"]["value"] > 0


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_is_not_correct(tree, name):
    """The reference with every matmul operand rounded to 16 bits, in the
    program's place, fails one of the cell's numbers on every seed."""
    import json
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    spec = cellmod.load(tree / "chipbench", bench, name)
    with jax.default_matmul_precision("highest"):
        c = cellmod.Cell(spec, jax.devices()[:CELLS[name][1]])
        for seed in (1, 2, 3):
            with jax.set_mesh(c.mesh):
                c.setup(seed)
                c.free()
            ref = c.check()
            ctrl = c.numbers(ref, calibrate.as_program(
                c.check(precision="bf16x2")))
            assert any(ctrl[k] > v for k, v in spec["limits"].items()), ctrl


def _unchanged(real, _cell):
    def chunk(params, state, xs, ys, lr):
        keep = jax.tree.map(jnp.copy, (params, state))
        _p, _s, losses, pers, g = real(params, state, xs, ys, lr)
        return (*keep, losses, pers, g)
    return chunk


def _half_batch(real, _cell):
    def chunk(params, state, xs, ys, lr):
        h = xs.shape[1] // 2
        return real(params, state, xs[:, :h], ys[:, :h], lr)
    return chunk


def _no_exchange(real, cell):
    per_chip = cell.lp.num_members // cell.chips

    def chunk(params, state, xs, ys, lr):
        p, s, _losses, pers, g = real(params, state, xs, ys, lr)
        return p, s, pers[:, :per_chip].sum(axis=1), pers, g
    return chunk


FAULTS = [("tiny.t16", _unchanged), ("tiny.t16", _half_batch),
          ("tinydeep.t16", _unchanged), ("tinydeep.t16", _half_batch),
          ("tinyfused.4chip", _unchanged), ("tinyfused.4chip", _half_batch),
          ("tinyfused.4chip", _no_exchange)]


@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{n}-{f.__name__[1:]}" for n, f in FAULTS])
def test_planted_fault_is_not_correct(tree, name, fault):
    res = run_cell(tree, name, wrap=fault, chips=CELLS[name][1])
    assert not res["correct"], res["checks"]


def test_metric_and_layer_added_as_files(tree):
    """A per-layer metric (its reader) and a layer's trace names (two files
    in its directory) join the benchmark without editing any file."""
    import json
    bench_dir = tree / "chipbench"
    (bench_dir / "metrics" / "probe_patterns.py").write_text(
        "def read(ctx):\n"
        "    return float(len(ctx['layers']['probe']))\n")
    layer = bench_dir / "layers" / "probe"
    layer.mkdir()
    (layer / "a.json").write_text(json.dumps({"patterns": ["x", "y"]}))
    (layer / "b.json").write_text(json.dumps({"patterns": ["y", "z"]}))
    meta = json.loads((tree / "BENCHMARK.json").read_text())
    meta["per_layer"].append({
        "name": "probe_patterns", "unit": "n", "better": "higher",
        "source": "program_counter", "layer": "probe",
        "moves": "train_member_steps_per_s", "workloads": ["tiny.t16"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(meta))
    res = run_cell(tree, "tiny.t16", trace=1)
    assert res["correct"], res["checks"]
    assert res["metrics"]["probe_patterns"]["value"] == 3.0
    assert "train_input_wait_ms" in res["metrics"]
    # no device plane on the CPU: the trace readers find nothing to read
    assert "train_idle_share" not in res["metrics"]
    assert res["device"]["window_s"] > 0


def test_weights_are_layout_independent(tree):
    """A member's weights depend on the seed and the member, not on where
    the program's layout puts it: the packed program tree and the
    reference's blocks hold the same numbers."""
    import json

    import members as mb
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    spec = cellmod.load(tree / "chipbench", bench, "tinydeep.t16")
    c = cellmod.Cell(spec, jax.devices()[:1])
    key = mb.seed_words(11)
    params = jax.jit(c.layout.pack)(key, c.ix)
    got = c.layout.member_sumsq(params, c.segs)
    want = {}
    for blk in mb.blocks(c.members, 6, 3):
        m, real = mb.block_arrays(c.members, blk)
        ws, bs, _ = mb.block_weights(key, m, real)
        names = (["w_in"] + [f"mid{l}.w" for l in range(blk.depth - 1)]
                 + ["w_out"] + ["b_in"]
                 + [f"mid{l}.b" for l in range(blk.depth - 1)] + ["b_out"])
        for leaf, a in zip(names, ws + bs):
            v = want.setdefault(leaf, np.zeros(len(c.members)))
            sel = blk.idx >= 0
            v[blk.idx[sel]] = np.asarray(
                jnp.sum(a.reshape(a.shape[0], -1) ** 2, axis=1))[sel]
    canon = c.layout.canon
    for leaf, v in got.items():
        np.testing.assert_allclose(np.asarray(v)[canon >= 0],
                                   want[leaf][canon[canon >= 0]],
                                   rtol=1e-6)
