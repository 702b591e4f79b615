"""Fault tolerance: a job killed mid-run resumes from the last committed
checkpoint and produces the SAME final state as an uninterrupted run
(data is step-indexed → replay is bitwise)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import StragglerPolicy, TrainRunner
from repro.distributed.compression import (compressed_psum_tree,
                                           init_error_feedback, quantize_int8)


def _step_fn(state, step):
    # toy deterministic "training": params += f(step)
    g = jnp.asarray(np.sin(step + 1), jnp.float32)
    new = {"w": state["w"] + g, "n": state["n"] + 1}
    return new, {"loss": float(jnp.abs(g))}


def test_restart_reproduces_uninterrupted_run(tmp_path):
    init = {"w": jnp.zeros((4,)), "n": jnp.zeros((), jnp.int32)}
    # reference: no failure
    ref = TrainRunner(_step_fn, jax.tree.map(jnp.copy, init),
                      ckpt_dir=str(tmp_path / "ref"), ckpt_every=3)
    ref.run(10)

    # failing run: dies at steps 5 and 8 (after ckpts at 0,3 / 6)
    boom = {5: True, 8: True}

    def failure_hook(step):
        if boom.pop(step, False):
            raise RuntimeError(f"simulated chip failure at {step}")

    r = TrainRunner(_step_fn, jax.tree.map(jnp.copy, init),
                    ckpt_dir=str(tmp_path / "ft"), ckpt_every=3,
                    failure_hook=failure_hook)
    r.run(10)
    assert r.restarts == 2
    np.testing.assert_allclose(np.asarray(r.state["w"]),
                               np.asarray(ref.state["w"]), rtol=1e-6)
    assert int(r.state["n"]) == int(ref.state["n"])


def test_restart_without_checkpoint_replays_from_initial(tmp_path):
    """A failure BEFORE the first committed checkpoint replays from the
    runner's initial-state snapshot — completed steps are not applied twice
    (and a donation-deleted live state cannot poison the retry)."""
    boom = {1: True}

    def step_fn(state, step):
        if boom.pop(step, False):
            raise RuntimeError("transient failure, nothing on disk yet")
        return {"w": state["w"] + 1.0}, {"loss": 0.0}

    r = TrainRunner(step_fn, {"w": jnp.zeros(2)},
                    ckpt_dir=str(tmp_path / "none"), ckpt_every=0)
    r.run(3)
    np.testing.assert_allclose(np.asarray(r.state["w"]), 3.0)
    assert r.restarts == 1


def test_too_many_restarts_raises(tmp_path):
    def always_fail(step):
        raise RuntimeError("dead host")

    r = TrainRunner(_step_fn, {"w": jnp.zeros(1), "n": jnp.zeros((), jnp.int32)},
                    ckpt_dir=str(tmp_path), ckpt_every=100,
                    failure_hook=always_fail, max_restarts=2)
    with pytest.raises(RuntimeError, match="exceeded"):
        r.run(5)


def test_straggler_policy():
    pol = StragglerPolicy(timeout_s=0.5, max_strikes=2)
    pol.observe(0, 0.1)
    pol.observe(1, 0.9)            # strike 1
    pol.observe(2, 0.2)            # reset
    pol.observe(3, 0.9)
    with pytest.raises(TimeoutError):
        pol.observe(4, 0.9)
    assert len(pol.events) == 3


def test_quantize_roundtrip_error_feedback():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(0, 1, (256,)), jnp.float32)
    err = jnp.zeros_like(g)
    q, scale, err2 = quantize_int8(g, err)
    rec = q.astype(jnp.float32) * scale
    # per-element error bounded by one quantisation step…
    assert float(jnp.abs(rec - g).max()) <= float(scale) + 1e-7
    # …and exactly captured by the feedback residual
    np.testing.assert_allclose(np.asarray(rec + err2), np.asarray(g),
                               atol=1e-6)


def test_compressed_psum_single_axis():
    """On a 1-sized axis the compressed reduce must be a near-identity
    (quantisation only) and converge via error feedback."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("pod",))
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(0, 1, (64,)),
                          jnp.float32)}
    err = init_error_feedback(g)

    def f(gg, ee):
        return compressed_psum_tree(gg, ee, "pod")

    from jax.sharding import PartitionSpec as P
    spec = jax.tree.map(lambda _: P(), g)
    out, err2 = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=(spec, spec),
                      out_specs=(spec, spec), check_vma=False))(g, err)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=2e-2)
    # feeding the error back makes the two-step average exact-ish
    total = np.asarray(out["w"] + err2["w"])
    np.testing.assert_allclose(total, np.asarray(g["w"]), atol=1e-6)


_SHARDED_REPLAY = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh
from repro.core import deep
from repro.core.population import LayeredPopulation
from repro.distributed import TrainRunner
from repro.distributed.sharding import pop_axis_size, population_shardings
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh()
assert pop_axis_size(mesh) == 4
lp0 = LayeredPopulation(
    6, 3, widths=((7,), (13, 5), (16, 8), (13, 5), (9,), (12, 4)),
    activations=("relu", ("tanh", "gelu"), ("relu", "tanh"),
                 ("tanh", "gelu"), "relu", ("relu", "tanh")),
    block=8).sorted()
lp = lp0.shard_pad(pop_axis_size(mesh))

with set_mesh(mesh):
    p_sh = population_shardings(lp, mesh)
    params = jax.jit(
        lambda k: deep.pad_params(deep.init_params(k, lp0), lp0, lp,
                                  jax.random.fold_in(k, 1)),
        out_shardings=p_sh)(jax.random.PRNGKey(0))
    chunk = deep.make_population_train_step(lp, scan_steps=2)
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(0, 1, (8, 8, 6)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 3, (8, 8)).astype(np.int32))

    def make_step_fn():
        def step_fn(state, c):
            p, _, _ = chunk(state["params"], xs[2*c:2*c+2], ys[2*c:2*c+2],
                            0.05)
            return {"params": p}, {"loss": 0.0}
        return step_fn

    def run(ckpt_dir, failure_hook=None):
        # fresh copy per run: the donated chunk consumes its input tree
        state = {"params": jax.device_put(jax.tree.map(jnp.copy, params),
                                          p_sh)}
        runner = TrainRunner(
            make_step_fn(), state,
            ckpt_dir=ckpt_dir, ckpt_every=1, failure_hook=failure_hook,
            mesh=mesh, state_specs={"params": lp.param_specs()})
        runner.run(4)
        return runner

    ref = run(sys.argv[1] + "/ref")
    boom = {2: True}
    def hook(step):
        if boom.pop(step, False):
            raise RuntimeError("simulated chip failure")
    ft = run(sys.argv[1] + "/ft", failure_hook=hook)
    assert ft.restarts == 1

    # REGRESSION (ROADMAP PR-2 follow-up): the crash-restored state must
    # come back SHARDED over the population axis, not replicated
    w_in = ft.state["params"]["w_in"]
    assert not w_in.sharding.is_fully_replicated, str(w_in.sharding)
    assert "model" in str(w_in.sharding.spec), str(w_in.sharding)
    sharded_mid = [w for w in ft.state["params"]["mid"][0]["w"]
                   if not w.sharding.is_fully_replicated
                   and "model" in str(w.sharding.spec)]
    assert sharded_mid, [str(w.sharding) for w in
                         ft.state["params"]["mid"][0]["w"]]
    # and replay is bitwise (step-indexed data, committed checkpoint)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), ft.state, ref.state)
print("OK")
"""


@pytest.mark.slow
def test_sharded_crash_replay_stays_sharded(tmp_path):
    """On a 4-fake-device mesh, a mid-run failure replayed through
    ``TrainRunner(mesh=..., state_specs=...)`` restores the population
    state SHARDED (device_put through the layout's spec tree) and
    bitwise-equal to the uninterrupted run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    r = subprocess.run([sys.executable, "-c", _SHARDED_REPLAY,
                        str(tmp_path)],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_runner_derives_restore_shardings_from_specs(tmp_path):
    """The mesh + spec-tree wiring builds the same NamedSharding tree a
    caller would hand-build (single-device degenerate case)."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    state = {"w": jnp.zeros((8, 2))}
    r = TrainRunner(_step_fn, state, ckpt_dir=str(tmp_path), ckpt_every=0,
                    mesh=mesh, state_specs={"w": P("model", None)})
    assert r.restore_shardings is not None
    assert r.restore_shardings["w"].mesh.shape == dict(mesh.shape)


def test_restart_prints_its_exception(tmp_path, capsys):
    """A restart is never silent: the runner prints the step, the restart
    count and the exception before it replays."""
    boom = {1: True}

    def failure_hook(step):
        if boom.pop(step, False):
            raise RuntimeError("simulated chip failure at 1")

    r = TrainRunner(_step_fn, {"w": jnp.zeros(2), "n": jnp.zeros((), jnp.int32)},
                    ckpt_dir=str(tmp_path), ckpt_every=0,
                    failure_hook=failure_hook)
    r.run(3)
    assert r.restarts == 1
    err = capsys.readouterr().err
    assert "step 1 failed (restart 1/3)" in err, err
    assert "RuntimeError: simulated chip failure at 1" in err, err


def test_restore_lands_on_live_shardings(tmp_path):
    """A crash replay restores onto the shardings the live state had after
    the last completed step — the jitted step's OUTPUT shardings — not the
    spec-derived ones, which may be spelled differently and key a second
    executable."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    live = NamedSharding(mesh, P("model"))
    step = jax.jit(lambda w: w + 1.0, out_shardings=live)
    boom = {2: True}

    def failure_hook(s):
        if boom.pop(s, False):
            raise RuntimeError("simulated chip failure")

    init = jax.device_put(jnp.zeros((8, 2)), NamedSharding(mesh, P("model", None)))
    r = TrainRunner(lambda st, s: ({"w": step(st["w"])}, {"loss": 0.0}),
                    {"w": init}, ckpt_dir=str(tmp_path), ckpt_every=1,
                    failure_hook=failure_hook, mesh=mesh,
                    state_specs={"w": P("model", None)})
    seen = []
    r.on_restore = lambda s: seen.append(r.state["w"].sharding)
    r.run(4)
    assert r.restarts == 1
    assert seen == [live]
    np.testing.assert_array_equal(np.asarray(r.state["w"]), 4.0)


def test_elastic_remesh_preserves_values():
    from repro.distributed import elastic_remesh
    from jax.sharding import PartitionSpec as P
    state = {"w": jnp.arange(16.0).reshape(8, 2)}
    spec = {"w": P("data", None)}
    mesh, resharded = elastic_remesh(state, spec)
    np.testing.assert_array_equal(np.asarray(resharded["w"]),
                                  np.asarray(state["w"]))
