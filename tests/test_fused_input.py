"""Fused input layer (kernels/fused_input.py, DESIGN.md §9): dense
input-feature matmul + bias + per-segment activation + padding mask in ONE
Pallas pass, replacing the XLA dot + standalone activation pass for
layer 0 of the fused population path.  Interpret-mode equivalence vs the
XLA reference (``input_xla``) — values and per-operand gradients — on
ragged layouts, the wide-feature (F > 128, tiled reduction) path, tiles of
several hidden blocks by the whole batch (``tile_plan``), the in-kernel
bias gradient, the dx the backward makes only for a perturbed x, and the
routing of layer 0 by ``bd_impl``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.deep import (forward, fused_loss, init_params, input_fused,
                             input_xla)
from repro.core.population import LayeredPopulation
from repro.kernels import fused_input, ops, tiling
from _kernel_routing import kernel_names

LP = LayeredPopulation(20, 3, ((5, 3), (12, 9), (7,), (17, 9, 5)),
                       ("relu", "gelu", "tanh", "mish"), block=8)
# in_features > 128 exercises the tiled (block_f=128) reduction grid and
# the feature-axis padding (177 → 256) whose pad VJP must slice cotangents
LP_WIDE = LayeredPopulation(177, 3, ((9, 4), (24, 16), (6,)),
                            ("selu", "hardshrink", "sigmoid"), block=8)


def _inputs(lp, b=9, seed=0):
    params = init_params(jax.random.PRNGKey(seed), lp)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, lp.in_features))
    return x, params["w_in"], params["b_in"]


def _train_step_kernels(bd_impl, lp=LP):
    x, _, _ = _inputs(lp)
    params = init_params(jax.random.PRNGKey(0), lp)
    y = jnp.zeros((x.shape[0],), jnp.int32)
    return kernel_names(jax.grad(
        lambda p: fused_loss(p, x, y, lp, bd_impl=bd_impl)[0]), params)


def test_registry_has_fused():
    """The fused train step runs layer 0 through the fused input kernel,
    both directions."""
    assert {"fused_input_fwd", "fused_input_bwd"} <= _train_step_kernels(
        "fused")


def test_default_routing_follows_bd_impl():
    """Layer 0 follows ``bd_impl``: the fused path's train step and
    forward launch the fused input kernels, the einsum path's launch no
    kernel at all (XLA dot + sliced activation)."""
    x, _, _ = _inputs(LP)
    params = init_params(jax.random.PRNGKey(0), LP)
    assert _train_step_kernels("fused") == {
        "fused_input_fwd", "fused_input_bwd", "fused_mid_fwd",
        "fused_mid_bwd", "loss_head_fwd", "loss_head_bwd"}
    assert _train_step_kernels("einsum") == set()
    assert "fused_input_infer" in kernel_names(
        lambda p: forward(p, x, LP, bd_impl="fused", infer=True), params)
    assert kernel_names(lambda p: forward(p, x, LP, bd_impl="einsum"),
                        params) == set()


@pytest.mark.parametrize("lp", [LP, LP_WIDE], ids=["narrow", "wide_f"])
def test_forward_matches_xla(lp):
    x, w, b = _inputs(lp)
    ye = input_xla(x, w, b, lp)
    yf = input_fused(x, w, b, lp)
    np.testing.assert_allclose(np.asarray(ye), np.asarray(yf),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("lp", [LP, LP_WIDE], ids=["narrow", "wide_f"])
def test_grads_match_xla(lp):
    """dx, dW_in, db_in from the one-pass fused backward vs XLA autodiff —
    the feature-axis pad cotangent must slice back to the caller's F."""
    x, w, b = _inputs(lp, seed=3)
    ge = jax.grad(lambda *a: (input_xla(*a, lp) ** 2).sum(),
                  argnums=(0, 1, 2))(x, w, b)
    gf = jax.grad(lambda *a: (input_fused(*a, lp) ** 2).sum(),
                  argnums=(0, 1, 2))(x, w, b)
    for a, f in zip(ge, gf):
        assert f.shape == a.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(f),
                                   rtol=1e-4, atol=1e-5)


# Tiles of G hidden blocks by the whole batch.  A layout of 57 blocks of
# 8 lanes, its members (1..20 units) in one run per activation as a sorted
# population has them: at G = 8, 16 or 24 an activation run crosses a tile
# boundary, some tile holds several activations, and the last tile is
# partly filled (test_tiled_layout_covers_the_cases).  The tolerances are
# those of the one-tile cases above.
_TILED_WIDTHS = tuple((w,) for w in (3, 12, 20, 7, 17, 5, 9, 14, 2, 11,
                                     19, 8, 1, 16, 6, 13) * 2 + (4,))
_TILED_ACTS = tuple(("relu", "gelu", "tanh", "selu")[4 * i // 33]
                    for i in range(33))
LP_TILED = LayeredPopulation(20, 3, _TILED_WIDTHS, _TILED_ACTS, block=8)
LP_TILED_WIDE = LayeredPopulation(177, 3, _TILED_WIDTHS, _TILED_ACTS,
                                  block=8)
_TILES = (8, 16, 24)


def _tile(monkeypatch, b, g, lp=LP_TILED):
    """Steer the kernels to ``g`` blocks of 8 lanes per grid step at batch
    b through the bytes a step aims to move: a block brings b padded to 8
    rows of the activations and ``block_f`` columns of the weight."""
    block_f = fused_input.pick_block_f(
        fused_input.padded_features(lp.in_features))
    monkeypatch.setattr(tiling, "TILE_BYTES",
                        max(-(-b // 8) * 8, block_f) * 8 * 4 * g)
    assert fused_input.tile_plan(b, lp.layer_pop(0).total_hidden,
                                 lp.in_features, 8).g == g


def _check_grads(lp, b, seed, wrt=(0, 1, 2)):
    x, w, bias = _inputs(lp, b=b, seed=seed)
    ge = jax.grad(lambda *a: (input_xla(*a, lp) ** 2).sum(),
                  argnums=wrt)(x, w, bias)
    gf = jax.grad(lambda *a: (input_fused(*a, lp) ** 2).sum(),
                  argnums=wrt)(x, w, bias)
    for a, f in zip(ge, gf):
        assert f.shape == a.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(f),
                                   rtol=1e-4, atol=1e-4)


def test_tiled_layout_covers_the_cases():
    ids = np.asarray(LP_TILED.layer_pop(0).block_act_ids)
    nb = len(ids)
    runs = ids[np.r_[True, np.diff(ids) != 0]]
    assert len(runs) == len(set(runs)) == 4            # one run each
    for g in _TILES:
        assert nb % g                                  # partial last tile
        tiles = [ids[t:t + g] for t in range(0, nb, g)]
        assert any(len(set(t)) > 1 for t in tiles)     # several activations
        assert any(tiles[t][-1] == tiles[t + 1][0]     # a run crosses a
                   for t in range(len(tiles) - 1))     # tile boundary


@pytest.mark.parametrize("g", _TILES)
@pytest.mark.parametrize("b", [9, 32, 256])
def test_tiled_forward_matches_xla(monkeypatch, b, g):
    _tile(monkeypatch, b, g)
    x, w, bias = _inputs(LP_TILED, b=b, seed=b + g)
    np.testing.assert_allclose(
        np.asarray(input_xla(x, w, bias, LP_TILED)),
        np.asarray(input_fused(x, w, bias, LP_TILED)), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("g", _TILES)
@pytest.mark.parametrize("b", [9, 32, 256])
def test_tiled_grads_match_xla(monkeypatch, b, g):
    """dW_in and db_in per tile, dx summed over the tiles, at every tiling
    — so the result does not depend on G."""
    _tile(monkeypatch, b, g)
    _check_grads(LP_TILED, b, seed=2 * b + g)


@pytest.mark.parametrize("g", [8, 24])
def test_tiled_wide_f_matches_xla(monkeypatch, g):
    """F = 177 in two 128-lane reduction tiles under several hidden tiles:
    the forward sums each block over the feature tiles in its scratch
    columns, the backward writes one dW_in / dx stripe per feature tile."""
    _tile(monkeypatch, 32, g, LP_TILED_WIDE)
    x, w, bias = _inputs(LP_TILED_WIDE, b=32, seed=g)
    np.testing.assert_allclose(
        np.asarray(input_xla(x, w, bias, LP_TILED_WIDE)),
        np.asarray(input_fused(x, w, bias, LP_TILED_WIDE)),
        rtol=1e-5, atol=1e-6)
    _check_grads(LP_TILED_WIDE, 32, seed=g + 1)


def test_grads_match_multi_batch_tile(monkeypatch):
    """B = 300, more rows than one MXU pass, in every grid step: dW_in and
    db_in reduce the whole batch inside the step, dx sums the tiles."""
    _tile(monkeypatch, 300, 16)
    _check_grads(LP_TILED, 300, seed=5)


def test_tiling_does_not_change_result(monkeypatch):
    """The same inputs under G = 8 and under one tile for every block give
    the same values and gradients up to f32 summation order."""
    x, w, bias = _inputs(LP_TILED, b=32, seed=17)

    def run():
        y, vjp = jax.vjp(lambda *a: input_fused(*a, LP_TILED), x, w, bias)
        return (y,) + vjp(jnp.cos(y))

    one = run()
    _tile(monkeypatch, 32, 8)
    for a, f in zip(one, run()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(f),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("b", [32, 256])
def test_bias_grad_is_batch_sum_of_du(monkeypatch, b):
    """The backward's db row is Σ_b dy·g' of the forward's own g', summed
    in the order of the XLA column reduce it replaced (64-row groups, each
    vreg by vreg with its sublanes folded by halves, the groups in turn),
    and its dW rows are du^T·x."""
    _tile(monkeypatch, b, 8)
    p0 = LP_TILED.layer_pop(0)
    x, w, bias = _inputs(LP_TILED, b=b, seed=23)
    xp = jnp.pad(x, ((0, 0), (0, fused_input.padded_features(20) - 20)))
    wp = jnp.pad(w, ((0, 0), (0, xp.shape[1] - 20)))
    y, gp = fused_input.fused_input_fwd(
        xp, wp, bias.reshape(1, -1), jnp.asarray(p0.hidden_mask)[None],
        jnp.asarray(p0.block_act_ids, jnp.int32), block=8, block_b=b,
        with_deriv=True, interpret=True)
    dy = jax.random.normal(jax.random.PRNGKey(29), y.shape)
    dw, db, dx = fused_input.fused_input_bwd(dy, gp, xp, None, block=8,
                                             interpret=True)
    assert dx is None and db.shape == (p0.total_hidden,)
    du = np.asarray(dy) * np.asarray(gp)
    tot = None
    for g0 in range(0, b, 64):
        acc = du[g0:g0 + 8]
        for r in range(g0 + 8, min(g0 + 64, b), 8):
            acc = acc + du[r:r + 8]
        for h in (4, 2, 1):
            acc = acc[:h] + acc[h:2 * h]
        tot = acc if tot is None else tot + acc
    np.testing.assert_array_equal(np.asarray(db), tot[0])
    np.testing.assert_allclose(np.asarray(db), du.astype(np.float64).sum(0),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw), du.T @ np.asarray(xp),
                               rtol=1e-5, atol=1e-5)


def _bwd_outputs(fn, *args):
    """Output count of each traced ``fused_input_bwd`` launch."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    found = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if (e.primitive.name == "pallas_call" and "fused_input_bwd"
                    in str(e.params["name"])):
                found.append(len(e.outvars))
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    if isinstance(sub, ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, Jaxpr):
                        walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_param_grad_makes_no_dx(monkeypatch):
    """A training-style gradient (over W_in and b_in, the batch a constant)
    launches a backward with two outputs, dW_in and db_in, and no dx; a
    gradient that asks for x gets the third, and both match XLA."""
    _tile(monkeypatch, 32, 8)
    x, w, bias = _inputs(LP_TILED, b=32, seed=31)

    def params_only(w, bias):
        return (input_fused(x, w, bias, LP_TILED) ** 2).sum()

    def with_x(x, w, bias):
        return (input_fused(x, w, bias, LP_TILED) ** 2).sum()

    assert _bwd_outputs(jax.grad(params_only, argnums=(0, 1)), w, bias) \
        == [2]
    assert _bwd_outputs(jax.grad(with_x, argnums=(0, 1, 2)), x, w, bias) \
        == [3]
    _check_grads(LP_TILED, 32, seed=31, wrt=(1, 2))


@pytest.mark.parametrize("args,want", [
    # paper-40k, one chip's members: 16 blocks and 625 steps a pass
    ((256, 1_280_000, 100, 128), (16, 625, False)),
    # helena: 27 features, the same tiles
    ((256, 1_280_000, 27, 128), (16, 625, False)),
    # deep-1k's layer 0, 2,560 blocks, at batch 256 and 32: at 32 rows a
    # block brings more of w_in (104 columns) than of the activations
    ((256, 327_680, 100, 128), (16, 160, False)),
    ((32, 327_680, 100, 128), (39, 66, False)),
    # batch 9 pads to 16 rows
    ((9, 1_280_000, 100, 128), (39, 257, False)),
    # F = 784 in seven 128-lane reduction tiles
    ((256, 1_280_000, 784, 128), (16, 4_375, False)),
    # batch 8 with 128-column weight tiles: 2 MiB of w_in a step
    ((8, 1_280_000, 128, 128), (32, 313, False)),
    ((8, 1_280_000, 784, 128), (32, 2_191, False)),
])
def test_tile_plan(args, want):
    assert tuple(fused_input.tile_plan(*args)) == want


@pytest.mark.parametrize("args,kw,want", [
    # 2,048 blocks of 1 KiB make the step's 2 MiB; the cap holds it to 8
    ((100, 1024), {"cap": 8}, 8),
    # 20 blocks fit, rounded down to a multiple of 8; 3 fit, raised to 8
    ((100, tiling.TILE_BYTES // 20), {"multiple": 8}, 16),
    ((100, tiling.TILE_BYTES // 3), {"multiple": 8}, 8),
    # one step takes every block, whatever the multiple
    ((5, 1024), {"multiple": 8}, 5),
], ids=["cap", "multiple", "multiple-floor", "all-blocks"])
def test_tile_blocks(args, kw, want):
    assert tiling.tile_blocks(*args, **kw) == want


def test_tile_plan_dx_follows_x():
    assert fused_input.tile_plan(256, 1_280_000, 100, 128,
                                 x_perturbed=True).makes_dx


def test_bf16_operands_f32_epilogue():
    """bf16 x/W_in tiles, f32 accumulator + f32 bias/activation epilogue:
    tracks the XLA bf16 reference within bf16 tolerance."""
    x, w, b = _inputs(LP, seed=7)
    x16, w16 = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    ye = input_xla(x16, w16, b, LP)
    yf = input_fused(x16, w16, b, LP)
    np.testing.assert_allclose(np.asarray(ye, dtype=np.float32),
                               np.asarray(yf, dtype=np.float32),
                               rtol=2e-2, atol=2e-2)
