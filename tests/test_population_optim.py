"""The optimizer-generic population engine (core.deep.opt_step +
make_population_train_step(optimizer=...)): plain-SGD BIT-exactness against
the historical stateless step, momentum/AdamW trajectories through the
scanned chunk, per-member hyperparameter scale trees, global-norm grad
clipping, zero-moment shard padding, and opt-state sharding plumbing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import deep
from repro.core.population import LayeredPopulation
from repro.optim import (adamw, apply_updates, clip_by_global_norm,
                         global_norm, sgd)

LP = LayeredPopulation(
    6, 3,
    widths=((7,), (13, 5), (16, 8), (13, 5)),
    activations=("relu", ("tanh", "gelu"), ("relu", "tanh"),
                 ("tanh", "gelu")),
    block=8).sorted()


def _params():
    return deep.init_params(jax.random.PRNGKey(0), LP)


def _batch(b=9):
    return (jax.random.normal(jax.random.PRNGKey(1), (b, 6)),
            jax.random.randint(jax.random.PRNGKey(2), (b,), 0, 3))


def _tree_bit_eq(a, b, msg="bit drift"):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), msg


# --------------------------------------------------------------------- #
# THE acceptance regression: plain SGD through the engine is bit-exact  #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("lr", ["scalar", "vector"])
def test_opt_step_plain_sgd_bit_exact_vs_sgd_step(lr):
    """The optimizer-generic engine with sgd() (momentum 0) must reproduce
    the historical ``_sgd_update`` parameter trajectory BIT-for-bit —
    scalar and per-member-vector learning rates alike — so swapping the
    driver onto the engine perturbs no committed baseline."""
    x, y = _batch()
    lrv = 0.05 if lr == "scalar" else jnp.linspace(0.02, 0.08,
                                                   LP.num_members)
    opt = sgd()
    st = opt.init(_params())
    a = b = _params()
    for _ in range(4):
        a, la, pa = deep.sgd_step(a, x, y, lrv, LP)
        b, st, lb, pb, gn = deep.opt_step(b, st, x, y, lrv, opt, LP)
        assert gn is None
        assert np.array_equal(np.asarray(la), np.asarray(lb))
    _tree_bit_eq(a, b)
    assert int(st["count"]) == 4


def test_engine_chunk_plain_sgd_bit_exact_vs_legacy_chunk():
    """Same regression through the scanned chunk: the (params, opt_state)
    carry must not change a single bit of the plain-SGD params."""
    params = _params()
    xs = jax.random.normal(jax.random.PRNGKey(1), (5, 12, 6))
    ys = jax.random.randint(jax.random.PRNGKey(2), (5, 12), 0, 3)
    lrs = jnp.linspace(0.02, 0.08, LP.num_members)
    legacy = deep.make_population_train_step(LP, scan_steps=5, donate=False)
    engine = deep.make_population_train_step(LP, optimizer=sgd(),
                                             scan_steps=5, donate=False)
    p1, l1, pe1 = legacy(params, xs, ys, lrs)
    p2, st, l2, pe2, gn = engine(params, sgd().init(params), xs, ys, lrs)
    assert gn is None and int(st["count"]) == 5
    _tree_bit_eq(p1, p2)
    assert np.array_equal(np.asarray(l1), np.asarray(l2))
    assert np.array_equal(np.asarray(pe1), np.asarray(pe2))


# --------------------------------------------------------------------- #
# stateful trajectories through the chunk                               #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("make_opt", [
    lambda: sgd(momentum=0.9),
    lambda: adamw(weight_decay=0.01),
], ids=["momentum", "adamw"])
def test_chunk_matches_unscanned_reference_loop(make_opt):
    """The scanned chunk's stateful trajectory equals the hand-rolled
    opt.update/apply_updates loop (the same step math, no scan)."""
    params = _params()
    xs = jax.random.normal(jax.random.PRNGKey(1), (4, 12, 6))
    ys = jax.random.randint(jax.random.PRNGKey(2), (4, 12), 0, 3)
    opt = make_opt()

    p_ref, st_ref = params, opt.init(params)
    for t in range(4):
        (_, _), grads = jax.value_and_grad(deep.fused_loss, has_aux=True)(
            p_ref, xs[t], ys[t], LP)
        upd, st_ref = opt.update(grads, st_ref, p_ref, 0.05)
        p_ref = apply_updates(p_ref, upd)

    chunk = deep.make_population_train_step(LP, optimizer=make_opt(),
                                            scan_steps=4, donate=False)
    p_scan, st_scan, _, _, _ = chunk(params, opt.init(params), xs, ys, 0.05)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=1e-5, atol=1e-6), p_ref, p_scan)
    assert int(st_scan["count"]) == 4


def test_per_member_momentum_tree_equals_scalar_runs():
    """Members are independent, so a per-member momentum TREE must give
    each member exactly the trajectory of a whole-population run with that
    member's scalar momentum (values chosen exactly representable)."""
    params = _params()
    x, y = _batch()
    moms = [0.5, 0.875, 0.5, 0.875]
    tree_opt = sgd(momentum=deep.member_lr_tree(LP, jnp.asarray(moms)))
    p_tree, st = params, tree_opt.init(params)
    for _ in range(3):
        p_tree, st, *_ = deep.opt_step(p_tree, st, x, y, 0.05, tree_opt, LP)

    for mom in sorted(set(moms)):
        opt = sgd(momentum=mom)
        p_s, st_s = params, opt.init(params)
        for _ in range(3):
            p_s, st_s, *_ = deep.opt_step(p_s, st_s, x, y, 0.05, opt, LP)
        for m in range(LP.num_members):
            if moms[m] != mom:
                continue
            _tree_bit_eq(
                {k: v for k, v in
                 deep.extract_member(p_tree, LP, m).items()
                 if not isinstance(v, (str, tuple))},
                {k: v for k, v in deep.extract_member(p_s, LP, m).items()
                 if not isinstance(v, (str, tuple))},
                f"member {m} drifted under the momentum tree")


def test_grad_clip_applied_and_norm_reported():
    """--grad-clip semantics: the reported norm is the PRE-clip global
    norm and the update uses the clipped gradients."""
    params = _params()
    x, y = _batch()
    clip = 1e-2
    opt = sgd()
    p2, _, _, _, gnorm = deep.opt_step(params, opt.init(params), x, y,
                                       0.05, opt, LP, grad_clip=clip)
    grads = jax.grad(lambda p: deep.fused_loss(p, x, y, LP)[0])(params)
    np.testing.assert_allclose(float(gnorm), float(global_norm(grads)),
                               rtol=1e-6)
    assert float(gnorm) > clip  # the clip actually engaged
    clipped, _ = clip_by_global_norm(grads, clip)
    expect = jax.tree.map(lambda p, g: p - 0.05 * g, params, clipped)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7), p2, expect)


def test_engine_chunk_donates_params_and_state():
    params = _params()
    opt = sgd(momentum=0.9)
    st = opt.init(params)
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 6))
    ys = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 3)
    chunk = deep.make_population_train_step(LP, optimizer=opt, scan_steps=2)
    _ = chunk(params, st, xs, ys, 0.05)
    assert params["w_in"].is_deleted()
    assert st["mu"]["w_in"].is_deleted()
    with pytest.raises(ValueError, match="optimizer"):
        deep.make_population_train_step(LP, grad_clip=1.0)


# --------------------------------------------------------------------- #
# shard padding of optimizer state                                      #
# --------------------------------------------------------------------- #

def test_pad_state_zero_fillers_real_region_bit_exact():
    params = _params()
    opt = adamw(weight_decay=0.01, state_dtype=jnp.bfloat16)
    st = opt.init(params)
    x, y = _batch()
    for _ in range(2):
        params, st, *_ = deep.opt_step(params, st, x, y, 0.05, opt, LP)
    lpp = LP.shard_pad(3)
    padded = deep.pad_state(st, LP, lpp)
    # scalar count passes through; moments keep their (bf16) dtype
    assert int(padded["count"]) == int(st["count"])
    assert padded["m"]["w_in"].dtype == jnp.bfloat16
    # real region bit-identical, filler rows exactly zero
    h0 = LP.layer_pop(0).total_hidden
    np.testing.assert_array_equal(np.asarray(padded["m"]["w_in"][:h0]),
                                  np.asarray(st["m"]["w_in"]))
    assert not np.any(np.asarray(padded["m"]["w_in"][h0:],
                                 dtype=np.float32))
    assert not np.any(np.asarray(padded["v"]["b_out"][LP.num_members:],
                                 dtype=np.float32))
    # no-op when already aligned
    assert deep.pad_state(st, LP, LP) is st


def test_padded_momentum_trajectory_equals_unpadded():
    """pad_params + pad_state mid-run (the rung-boundary repack) leaves
    the real members' stateful trajectory identical to the unpadded run."""
    params = _params()
    opt = sgd(momentum=0.9)
    st = opt.init(params)
    x, y = _batch(16)
    for _ in range(2):
        params, st, *_ = deep.opt_step(params, st, x, y, 0.05, opt, LP)
    lpp = LP.shard_pad(3)
    padded = deep.pad_params(params, LP, lpp,
                             jax.random.fold_in(jax.random.PRNGKey(0), 1))
    st_p = deep.pad_state(st, LP, lpp)
    for _ in range(3):
        params, st, _, per_u, _ = deep.opt_step(params, st, x, y, 0.05,
                                                opt, LP)
        padded, st_p, _, per_p, _ = deep.opt_step(padded, st_p, x, y, 0.05,
                                                  opt, lpp)
    np.testing.assert_allclose(np.asarray(per_p[:LP.num_members]),
                               np.asarray(per_u), rtol=1e-5, atol=1e-6)
    for m in range(LP.num_members):
        a = deep.extract_member(params, LP, m)
        b = deep.extract_member(padded, lpp, m)
        jax.tree.map(lambda x_, y_: None if isinstance(x_, str)
                     else np.testing.assert_allclose(
                         np.asarray(x_), np.asarray(y_),
                         rtol=1e-5, atol=1e-6), a, b)


def test_pad_state_rejects_unpaddable_leaves():
    with pytest.raises(ValueError, match="params-shaped"):
        deep.pad_state({"weird": jnp.zeros((3,))}, LP, LP.shard_pad(3))


# --------------------------------------------------------------------- #
# sharding plumbing                                                     #
# --------------------------------------------------------------------- #

def test_population_opt_shardings_structure():
    """population_opt_shardings returns one NamedSharding per state leaf
    (momentum moments follow their parameters; count replicates)."""
    from repro.launch.mesh import make_mesh
    from repro.distributed.sharding import population_opt_shardings
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = sgd(momentum=0.9)
    sh = population_opt_shardings(LP, opt, mesh)
    state = opt.init(_params())
    assert (jax.tree_util.tree_structure(jax.tree.map(lambda s: 0, sh))
            == jax.tree_util.tree_structure(jax.tree.map(lambda x: 0,
                                                         state)))
    born = jax.jit(opt.init, out_shardings=sh)(_params())
    assert int(born["count"]) == 0


# --------------------------------------------------------------------- #
# per-leaf hyperparameter trees at the optimizer layer                  #
# --------------------------------------------------------------------- #

def test_adamw_per_member_weight_decay_tree():
    """A weight-decay scale tree decays each member's params by its own
    coefficient (checked against per-scalar whole-population runs)."""
    params = _params()
    x, y = _batch()
    wds = [0.0, 0.25, 0.0, 0.25]
    tree_opt = adamw(weight_decay=deep.member_lr_tree(LP, jnp.asarray(wds)))
    p_tree, st = params, tree_opt.init(params)
    for _ in range(2):
        p_tree, st, *_ = deep.opt_step(p_tree, st, x, y, 0.05, tree_opt, LP)
    for wd in sorted(set(wds)):
        opt = adamw(weight_decay=wd)
        p_s, st_s = params, opt.init(params)
        for _ in range(2):
            p_s, st_s, *_ = deep.opt_step(p_s, st_s, x, y, 0.05, opt, LP)
        for m in range(LP.num_members):
            if wds[m] != wd:
                continue
            a = deep.extract_member(p_tree, LP, m)
            b = deep.extract_member(p_s, LP, m)
            jax.tree.map(lambda x_, y_: None if isinstance(x_, str)
                         else np.testing.assert_allclose(
                             np.asarray(x_), np.asarray(y_),
                             rtol=1e-6, atol=1e-7), a, b)


def test_broadcast_scale_rejects_raw_vectors_and_bad_structure():
    from repro.optim import broadcast_scale, hyper_on
    params = {"a": jnp.zeros((2,)), "b": jnp.zeros((3,))}
    with pytest.raises(ValueError, match="momentum"):
        broadcast_scale(jnp.zeros((4,)), params, "momentum")
    with pytest.raises(ValueError, match="structure"):
        broadcast_scale({"a": 1.0}, params, "weight_decay")
    assert hyper_on({"a": 0.0}) and hyper_on(0.1) and not hyper_on(0.0)
