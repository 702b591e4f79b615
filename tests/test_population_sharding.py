"""Distribution-native layered populations: spec emission, member-count
shard padding, the scanned/donated train chunk, and (in a forced 4-device
subprocess) sharded-vs-single-device training equality with mid-layer
bucket params actually sharded over the model axis."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import deep
from repro.core.population import LayeredPopulation

LP = LayeredPopulation(
    6, 3,
    widths=((7,), (13, 5), (64, 32, 16), (13, 5)),
    activations=("relu", ("tanh", "gelu"), ("mish", "sigmoid", "tanh"),
                 ("tanh", "gelu")),
    block=8).sorted()


def test_param_specs_structure_matches_params():
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    specs = LP.param_specs()
    assert (jax.tree_util.tree_structure(
        specs, is_leaf=lambda s: isinstance(s, P))
        == jax.tree_util.tree_structure(jax.tree.map(lambda x: 0, params)))
    # member-major axes carry the population axis
    assert specs["w_in"] == P("model", None)
    assert specs["w_out"] == P(None, "model")
    assert specs["b_out"] == P("model", None)
    for lay in specs["mid"]:
        assert lay["b"] == P("model")
        for s in lay["w"]:
            assert s == P("model", None, None)


def test_opt_specs_structure_matches_state():
    from repro.optim import sgd
    opt = sgd(momentum=0.9)
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    state = opt.init(params)
    specs = LP.opt_specs(opt)
    assert (jax.tree_util.tree_structure(
        specs, is_leaf=lambda s: isinstance(s, P))
        == jax.tree_util.tree_structure(jax.tree.map(lambda x: 0, state)))


@pytest.mark.parametrize("n_shards", [2, 3, 4, 6])
def test_shard_pad_divisibility(n_shards):
    lp = LP.shard_pad(n_shards)
    assert lp.num_members % n_shards == 0
    for l in range(lp.depth):
        assert lp.layer_pop(l).total_hidden % (n_shards * lp.block) == 0
    # pads are trailing, identity-activated, full-depth
    assert lp.num_real == LP.num_members
    assert lp.widths[:lp.num_real] == LP.widths
    for m in range(lp.num_real, lp.num_members):
        assert lp.activations[m] == ("identity",) * lp.depth
    # idempotent once aligned
    assert lp.shard_pad(n_shards) == lp
    # no-op cases
    assert LP.shard_pad(1) == LP


def test_shard_pad_sorted_keeps_pads_trailing():
    lp = LP.shard_pad(4).sorted()
    assert lp.num_real == LP.num_members
    for m in range(lp.num_real, lp.num_members):
        assert lp.activations[m] == ("identity",) * lp.depth


def test_pad_params_real_region_bit_identical():
    lp = LP.shard_pad(3)
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    padded = deep.pad_params(params, LP, lp,
                             jax.random.fold_in(jax.random.PRNGKey(0), 1))
    p0 = LP.layer_pop(0)
    h0 = p0.total_hidden
    np.testing.assert_array_equal(np.asarray(padded["w_in"][:h0]),
                                  np.asarray(params["w_in"]))
    np.testing.assert_array_equal(np.asarray(padded["b_out"][:LP.num_members]),
                                  np.asarray(params["b_out"]))
    for l in range(LP.depth - 1):
        for bi, w in enumerate(params["mid"][l]["w"]):
            np.testing.assert_array_equal(
                np.asarray(padded["mid"][l]["w"][bi]), np.asarray(w))
    np.testing.assert_array_equal(
        np.asarray(padded["w_out"][:, :LP.layer_pop(LP.depth - 1).total_hidden]),
        np.asarray(params["w_out"]))


def test_pad_members_train_like_fillers_dont_leak():
    """Training the padded population leaves the real members' trajectory
    identical to the unpadded one (the pads are just more independent
    members)."""
    lp = LP.shard_pad(3)
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    padded = deep.pad_params(params, LP, lp,
                             jax.random.fold_in(jax.random.PRNGKey(0), 1))
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 6))
    y = jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 3)
    for _ in range(3):
        params, _, per_u = deep.sgd_step(params, x, y, 0.05, LP)
        padded, _, per_p = deep.sgd_step(padded, x, y, 0.05, lp)
    np.testing.assert_allclose(np.asarray(per_p[:LP.num_members]),
                               np.asarray(per_u), rtol=1e-5, atol=1e-6)


def test_scanned_chunk_equals_per_step_loop():
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    xs = jax.random.normal(jax.random.PRNGKey(1), (5, 12, 6))
    ys = jax.random.randint(jax.random.PRNGKey(2), (5, 12), 0, 3)
    lrs = jnp.array([0.05, 0.1, 0.02, 0.07])

    p_loop = params
    loop_losses = []
    for i in range(5):
        p_loop, loss, _ = deep.sgd_step(p_loop, xs[i], ys[i], lrs, LP)
        loop_losses.append(float(loss))

    chunk = deep.make_population_train_step(LP, scan_steps=5, donate=False)
    p_scan, losses, pers = chunk(params, xs, ys, lrs)
    assert pers.shape == (5, LP.num_members)
    np.testing.assert_allclose(np.asarray(losses), np.asarray(loop_losses),
                               rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), p_loop, p_scan)


def test_make_population_train_step_donates():
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 6))
    ys = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 3)
    chunk = deep.make_population_train_step(LP, scan_steps=2)
    _ = chunk(params, xs, ys, 0.05)
    assert params["w_in"].is_deleted()  # the donated tree was consumed
    with pytest.raises(ValueError):
        deep.make_population_train_step(LP, scan_steps=0)


def _masked_oracle_loss(params, x, y, lp):
    """The layered forward with every activation from the branchless masked
    select (the activation oracle) and the plain XLA projections, then the
    per-member NLL — independent of both paths' activation code."""
    from repro.core.activations import apply_activations_masked
    from repro.core.m3 import m3

    def act(z, l):
        pop = lp.layer_pop(l)
        return (apply_activations_masked(z, pop.act_ids)
                * jnp.asarray(pop.hidden_mask))

    h = act(x @ params["w_in"].T + params["b_in"], 0)
    for l in range(lp.depth - 1):
        z = deep.block_diag_einsum(h, params["mid"][l]["w"], lp, l)
        h = act(z + params["mid"][l]["b"]
                * jnp.asarray(lp.active_unit_mask(l + 1)), l + 1)
    logits = m3(h, params["w_out"], lp.layer_pop(lp.depth - 1)) \
        + params["b_out"][None]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None, None], axis=-1)[..., 0]
    return nll.mean(axis=0).sum(), logits


@pytest.mark.parametrize("bd_impl", deep.BD_CHOICES)
def test_bd_impl_matches_masked_oracle(bd_impl):
    """Both paths agree with the masked-activation oracle — forward AND
    gradients, through the whole deep net."""
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    x = jax.random.normal(jax.random.PRNGKey(1), (9, 6))
    y = jax.random.randint(jax.random.PRNGKey(2), (9,), 0, 3)
    _, want = _masked_oracle_loss(params, x, y, LP)
    got = deep.forward(params, x, LP, bd_impl=bd_impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    ga = jax.grad(lambda p: deep.fused_loss(p, x, y, LP,
                                            bd_impl=bd_impl)[0])(params)
    gb = jax.grad(lambda p: _masked_oracle_loss(p, x, y, LP)[0])(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), ga, gb)


def test_population_shardings_single_device():
    """population_shardings degrades to replication on the 1-device CPU
    (no mesh axes to shard over) but returns a full NamedSharding tree."""
    from repro.launch.mesh import make_mesh
    from repro.distributed.sharding import population_shardings
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = population_shardings(LP, mesh)
    leaves = jax.tree.leaves(sh)
    assert leaves and all(hasattr(s, "spec") for s in leaves)


_BATCH_SHARDING = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.distributed.sharding import population_batch_shardings
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(model=2)          # (data=2, model=2)
assert dict(mesh.shape) == {"data": 2, "model": 2}

# dividing batch: the batch axis actually shards over 'data'
sh_x, sh_y = population_batch_shardings(mesh, 8)
xs = jax.device_put(np.zeros((3, 8, 6), np.float32), sh_x)
ys = jax.device_put(np.zeros((3, 8), np.int32), sh_y)
assert not xs.sharding.is_fully_replicated, str(xs.sharding)
assert "data" in str(xs.sharding.spec) and "data" in str(ys.sharding.spec)
# ...and the leading scan axis stays whole on every device
assert xs.addressable_shards[0].data.shape == (3, 4, 6)

# non-dividing batch: documented fallback to replication
sh_x7, _ = population_batch_shardings(mesh, 7)
x7 = jax.device_put(np.zeros((3, 7, 6), np.float32), sh_x7)
assert x7.sharding.is_fully_replicated, str(x7.sharding)
print("OK")
"""


@pytest.mark.slow
def test_population_batch_shardings_data_axis(tmp_path):
    """Train batches shard over the mesh 'data' axis (scan axis whole,
    batch axis split), degrading to replication when the batch size
    doesn't divide the axis."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    r = subprocess.run([sys.executable, "-c", _BATCH_SHARDING],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


_SHARDED_DRIVER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import jax, jax.numpy as jnp, numpy as np
from repro.launch.train import main

params, lp = main([
    "--arch", "parallelmlp-10k", "--reduced", "--steps", "10",
    "--population-depths", "16,8;16,8;12,4;12,4;7;9", "--population-acts",
    "relu,tanh", "--scan-steps", "5", "--ckpt-every", "0",
    "--ckpt-dir", sys.argv[1] + "/ck"])
assert len(jax.devices()) == 4
# mid-layer bucket params must ACTUALLY shard over the model axis
sharded = [w for w in params["mid"][0]["w"]
           if not w.sharding.is_fully_replicated
           and "model" in str(w.sharding.spec)]
assert sharded, [str(w.sharding) for w in params["mid"][0]["w"]]
from repro.core.selection import evaluate_population
from repro.data import TabularTask
task = TabularTask(2048, lp.in_features, n_classes=lp.out_features, seed=0)
(_, _), (xte, yte) = task.split()
losses, _ = evaluate_population(params, lp, jnp.asarray(xte),
                                jnp.asarray(yte))
with open(sys.argv[1] + "/losses.json", "w") as f:
    json.dump({"losses": np.asarray(losses)[:lp.num_real].tolist(),
               "num_real": lp.num_real, "n_pad": lp.n_pad}, f)
print("OK")
"""


@pytest.mark.slow
def test_sharded_equals_single_device_training(tmp_path):
    """Acceptance: on a 4-fake-device host mesh, sharded run_population
    training produces per-member losses equal (to float tolerance) to the
    single-device run, with mid-layer buckets sharded over 'model'."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    r = subprocess.run([sys.executable, "-c", _SHARDED_DRIVER,
                        str(tmp_path)],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    with open(tmp_path / "losses.json") as f:
        sharded = json.load(f)
    assert sharded["n_pad"] > 0  # 6 members on 4 shards: padding exercised

    # identical run, single device, in-process
    from repro.core.selection import evaluate_population
    from repro.data import TabularTask
    from repro.launch.train import main
    params, lp = main([
        "--arch", "parallelmlp-10k", "--reduced", "--steps", "10",
        "--population-depths", "16,8;16,8;12,4;12,4;7;9",
        "--population-acts", "relu,tanh", "--scan-steps", "5",
        "--ckpt-every", "0", "--ckpt-dir", str(tmp_path / "ck1")])
    assert lp.n_pad == 0
    task = TabularTask(2048, lp.in_features, n_classes=lp.out_features,
                       seed=0)
    (_, _), (xte, yte) = task.split()
    losses, _ = evaluate_population(params, lp, jnp.asarray(xte),
                                    jnp.asarray(yte))
    np.testing.assert_allclose(
        np.asarray(sharded["losses"]),
        np.asarray(losses)[:sharded["num_real"]], rtol=2e-5, atol=2e-6)
