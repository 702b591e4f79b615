"""Layered populations (the unified engine): heterogeneous member DEPTHS and
per-layer activations stay exactly independent under fused training, and the
fused block-diagonal Pallas layer agrees with the einsum bucket loop plus
bias and activation — values and gradients — over odd widths/buckets."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.activations import ACTIVATIONS
from repro.core.deep import (BD_CHOICES, _act, block_diag_einsum,
                             block_diag_fused, extract_member, forward,
                             fused_loss, init_params, member_forward,
                             member_lr_tree, sgd_step)
from repro.core.population import LayeredPopulation

# widths (7,), (13, 5), (64, 32, 16) — the acceptance-criteria mix — plus a
# duplicate-shape member and per-layer activations.
LP = LayeredPopulation(
    in_features=6, out_features=3,
    widths=((7,), (13, 5), (64, 32, 16), (13, 5)),
    activations=("relu", ("tanh", "gelu"), ("mish", "sigmoid", "tanh"),
                 ("tanh", "gelu")),
    block=8)


def test_mixed_depth_forward_matches_members():
    params = init_params(jax.random.PRNGKey(0), LP)
    x = jax.random.normal(jax.random.PRNGKey(1), (9, 6))
    fused = forward(params, x, LP)
    for m in range(LP.num_members):
        want = member_forward(extract_member(params, LP, m), x)
        np.testing.assert_allclose(np.asarray(fused[:, m]), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=f"member {m}")


def _standalone_step(member, x, y, lr):
    acts = member["activations"]

    def loss(flat):
        w_in, b_in, mids, w_out, b_out = flat
        h = ACTIVATIONS[acts[0]](x @ w_in.T + b_in)
        for l, (w, b) in enumerate(mids):
            h = ACTIVATIONS[acts[l + 1]](h @ w.T + b)
        logits = h @ w_out.T + b_out
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()

    flat = (member["w_in"], member["b_in"],
            tuple((l["w"], l["b"]) for l in member["mid"]),
            member["w_out"], member["b_out"])
    g = jax.grad(loss)(flat)
    new = jax.tree.map(lambda p, gg: p - lr * gg, flat, g)
    return {"w_in": new[0], "b_in": new[1],
            "mid": [{"w": w, "b": b} for w, b in new[2]],
            "w_out": new[3], "b_out": new[4], "activations": acts}


@pytest.mark.parametrize("bd_impl", BD_CHOICES)
def test_heterogeneous_depth_training_is_independent(bd_impl):
    """Fused SGD over mixed depths + per-member learning rates equals every
    member trained standalone (acceptance criterion: ≤1e-4 after ≥3 steps)."""
    params = init_params(jax.random.PRNGKey(42), LP)
    members = [extract_member(params, LP, m) for m in range(LP.num_members)]
    lrs = jnp.array([0.05, 0.1, 0.02, 0.07])
    key = jax.random.PRNGKey(7)
    for _ in range(4):
        key, k1, k2 = jax.random.split(key, 3)
        x = jax.random.normal(k1, (16, 6))
        y = jax.random.randint(k2, (16,), 0, 3)
        params, _, _ = sgd_step(params, x, y, lrs, LP, bd_impl=bd_impl)
        members = [_standalone_step(mem, x, y, float(lrs[m]))
                   for m, mem in enumerate(members)]
    for m in range(LP.num_members):
        got, want = extract_member(params, LP, m), members[m]
        np.testing.assert_allclose(
            np.asarray(got["w_in"]), np.asarray(want["w_in"]),
            rtol=1e-4, atol=1e-5, err_msg=f"member {m} w_in")
        for l in range(len(want["mid"])):
            np.testing.assert_allclose(
                np.asarray(got["mid"][l]["w"]),
                np.asarray(want["mid"][l]["w"]), rtol=1e-4, atol=1e-5,
                err_msg=f"member {m} mid {l} — cross-member leak!")
            np.testing.assert_allclose(
                np.asarray(got["mid"][l]["b"]),
                np.asarray(want["mid"][l]["b"]), rtol=1e-4, atol=1e-5,
                err_msg=f"member {m} mid-bias {l}")
        np.testing.assert_allclose(
            np.asarray(got["w_out"]), np.asarray(want["w_out"]),
            rtol=1e-4, atol=1e-5, err_msg=f"member {m} w_out")


@pytest.mark.parametrize("widths,acts,block", [
    (((3,), (5, 2), (9, 7, 4)), ("relu", "tanh", "gelu"), 4),
    (((1, 1), (2, 3), (2, 3), (6, 6)), ("relu", "relu", "tanh", "mish"), 8),
    (((11, 3, 5), (4,), (11, 3, 5)), ("gelu", "sigmoid", "gelu"), 2),
])
def test_block_diag_fused_matches_einsum(widths, acts, block):
    """The fused mid layer (interpret) vs the einsum bucket loop + bias +
    sliced activation over odd widths and bucket patterns, values AND
    gradients (h, weights, bias), every mid layer."""
    lp = LayeredPopulation(5, 2, widths, acts, block=block)
    params = init_params(jax.random.PRNGKey(3), lp)
    x = jax.random.normal(jax.random.PRNGKey(4), (7, 5))
    for l in range(lp.depth - 1):
        w, b = params["mid"][l]["w"], params["mid"][l]["b"]
        h = jax.random.normal(jax.random.PRNGKey(10 + l),
                              (7, lp.layer_pop(l).total_hidden))

        def ref(hh, ww, bb):
            z = block_diag_einsum(hh, ww, lp, l) + bb * jnp.asarray(
                lp.active_unit_mask(l + 1), jnp.float32)
            return _act(lp, l + 1, z)

        def fus(hh, ww, bb):
            return block_diag_fused(hh, ww, lp, l, bias=bb)

        np.testing.assert_allclose(np.asarray(ref(h, w, b)),
                                   np.asarray(fus(h, w, b)),
                                   rtol=1e-5, atol=1e-6)
        ge = jax.grad(lambda *a: (ref(*a) ** 2).sum(), argnums=(0, 1, 2))(
            h, w, b)
        gf = jax.grad(lambda *a: (fus(*a) ** 2).sum(), argnums=(0, 1, 2))(
            h, w, b)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), ge, gf)
    # whole-network logits agreement (acceptance criterion: 1e-5)
    ye = forward(params, x, lp, bd_impl="einsum")
    yf = forward(params, x, lp, bd_impl="fused")
    np.testing.assert_allclose(np.asarray(ye), np.asarray(yf),
                               rtol=1e-5, atol=1e-6)


def test_passthrough_slices_carry_final_activations():
    """A depth-1 member's slice in later layers is EXACTLY its layer-0
    activations (identity pass-through: no weight, no bias, no activation)."""
    lp = LayeredPopulation(4, 2, ((6,), (5, 5, 5)), ("tanh", "relu"), block=4)
    params = init_params(jax.random.PRNGKey(0), lp)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 4))
    p0 = lp.layer_pop(0)
    h0 = jnp.tanh(x @ params["w_in"][p0.member_slice(0)].T
                  + params["b_in"][p0.member_slice(0)])
    # run the fused stack up to the last hidden layer
    h = _act(lp, 0, x @ params["w_in"].T + params["b_in"])
    for l in range(lp.depth - 1):
        h = block_diag_einsum(h, params["mid"][l]["w"], lp, l)
        h = h + params["mid"][l]["b"] * jnp.asarray(
            lp.active_unit_mask(l + 1), h.dtype)
        h = _act(lp, l + 1, h)
        sl = lp.layer_pop(l + 1).member_slice(0)
        np.testing.assert_allclose(np.asarray(h[:, sl]), np.asarray(h0),
                                   rtol=1e-6, atol=1e-6)
        # pass-through bias must be exactly zero (it is masked, not trained)
        np.testing.assert_array_equal(
            np.asarray(params["mid"][l]["b"][sl]), 0.0)


def test_member_lr_tree_structure():
    lrs = jnp.arange(1.0, LP.num_members + 1)
    tree = member_lr_tree(LP, lrs)
    params = init_params(jax.random.PRNGKey(0), LP)
    assert (jax.tree_util.tree_structure(tree)
            == jax.tree_util.tree_structure(params))
    # every scale leaf broadcasts against its parameter leaf
    jax.tree.map(lambda p, s: np.broadcast_shapes(p.shape, s.shape),
                 params, tree)


def test_validation():
    with pytest.raises(ValueError):  # activation list length != depth
        LayeredPopulation(4, 2, ((3, 4),), (("relu",),))
    with pytest.raises(ValueError):  # unknown activation
        LayeredPopulation(4, 2, ((3,),), ("nope",))
    with pytest.raises(ValueError):  # empty widths
        LayeredPopulation(4, 2, ((),), ("relu",))


def test_grid_and_sorted_bucket_compaction():
    lp = LayeredPopulation.grid(
        8, 2, [(4,), (4, 4), (6, 3)], ("relu", "tanh"), repeats=2, block=4)
    assert lp.num_members == 12
    # sorted: equal (depth, padded widths, act) members are contiguous →
    # bucket count per projection is bounded by the number of shape classes
    for l in range(lp.depth - 1):
        assert len(lp.proj_buckets(l)) <= 6


def test_optimizer_per_member_lr_tree():
    """The optim layer takes a member_lr_tree as ``lr`` directly."""
    from repro.optim import apply_updates, sgd
    params = init_params(jax.random.PRNGKey(0), LP)
    opt = sgd()
    state = opt.init(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 3)
    grads = jax.grad(lambda p: fused_loss(p, x, y, LP)[0])(params)
    lrs = jnp.full((LP.num_members,), 0.05)
    upd_tree, _ = opt.update(grads, state, params, member_lr_tree(LP, lrs))
    upd_scal, _ = opt.update(grads, state, params, 0.05)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
        upd_tree, upd_scal)
    apply_updates(params, upd_tree)  # structure round-trips


def test_population_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import restore_population, save_population
    params = init_params(jax.random.PRNGKey(0), LP)
    save_population(str(tmp_path), 5, params, LP)
    got, lp2, step = restore_population(str(tmp_path))
    assert step == 5 and lp2 == LP
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), params, got)


def test_selection_over_layered_population():
    from repro.core.selection import (evaluate_population, leaderboard,
                                      select_best)
    params = init_params(jax.random.PRNGKey(0), LP)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 6))
    y = jax.random.randint(jax.random.PRNGKey(2), (32,), 0, 3)
    losses, accs = evaluate_population(params, LP, x, y)
    assert losses.shape == (LP.num_members,)
    m, best = select_best(params, LP, losses)
    want = member_forward(best, x)
    fused = forward(params, x, LP)
    np.testing.assert_allclose(np.asarray(fused[:, m]), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    rows = leaderboard(LP, losses, accs, k=3)
    assert rows[0]["loss"] <= rows[-1]["loss"]
    assert isinstance(rows[0]["hidden"], tuple)
