"""Fused mid-layer kernel (kernels/fused_layer.py, DESIGN.md §7/§9):
projection + bias + per-segment activation in one Pallas pass, with the
ONE-PASS backward (dy·act'(z) formed in-register inside a two-level
param-tile × batch-tile grid that emits dx AND dw from a single launch at
any batch size).  Interpret-mode equivalence vs the einsum reference —
values AND gradients — across every paper activation, ragged segment
layouts, multi-batch-tile shapes (B > block_b), the shard_pad
filler-member case, and the bf16 mixed-precision policy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.activations import ACTIVATION_ORDER
from repro.core.deep import (BD_CHOICES, _act, block_diag_einsum,
                             block_diag_fused, forward, fused_loss,
                             init_params, make_population_train_step,
                             pad_params, sgd_step)
from repro.core.population import LayeredPopulation
from _kernel_routing import kernel_names

# one member per paper activation, ragged widths AND ragged depths: every
# bucket shape (odd fan-ins, duplicate shapes, pass-throughs) in one layout
_WIDTHS = ((5, 3), (12, 9), (7,), (17, 9, 5), (8, 8),
           (5, 3), (3, 11, 2), (24, 16), (4,), (9, 9, 9))
LP_ALL = LayeredPopulation(6, 3, _WIDTHS, ACTIVATION_ORDER, block=8)


def _params_and_batch(lp, b=9, seed=0):
    params = init_params(jax.random.PRNGKey(seed), lp)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, lp.in_features))
    y = jax.random.randint(jax.random.PRNGKey(seed + 2), (b,), 0,
                           lp.out_features)
    return params, x, y


def test_registry_has_fused():
    """``bd_impl`` takes exactly the XLA path and the fused one; the fused
    train step's mid layers run the fused mid-layer kernel."""
    assert BD_CHOICES == ("einsum", "fused")
    params, x, y = _params_and_batch(LP_ALL)
    names = kernel_names(jax.grad(
        lambda p: fused_loss(p, x, y, LP_ALL, bd_impl="fused")[0]), params)
    assert {"fused_mid_fwd", "fused_mid_bwd"} <= names


@pytest.mark.parametrize("bad", ["pallas", "cutlass"])
def test_forward_and_train_step_refuse_unknown_impl(bad):
    """A removed or typo'd implementation fails loudly, naming the two
    that exist, instead of falling back to another one."""
    params, x, _ = _params_and_batch(LP_ALL)
    with pytest.raises(ValueError, match="einsum.*fused"):
        forward(params, x, LP_ALL, bd_impl=bad)
    with pytest.raises(ValueError, match="einsum.*fused"):
        make_population_train_step(LP_ALL, bd_impl=bad)


def test_forward_matches_einsum_every_activation():
    params, x, _ = _params_and_batch(LP_ALL)
    ye = forward(params, x, LP_ALL, bd_impl="einsum")
    yf = forward(params, x, LP_ALL, bd_impl="fused")
    np.testing.assert_allclose(np.asarray(ye), np.asarray(yf),
                               rtol=1e-5, atol=1e-6)


def test_grad_matches_einsum_every_activation():
    params, x, y = _params_and_batch(LP_ALL)

    def loss(impl):
        return lambda p: fused_loss(p, x, y, LP_ALL, bd_impl=impl)[0]

    ge = jax.grad(loss("einsum"))(params)
    gf = jax.grad(loss("fused"))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6), ge, gf)


def test_grad_matches_multi_batch_tile_one_pass():
    """Batch > 128 pads to several batch tiles → the TWO-LEVEL-GRID
    one-pass backward (param-tile outer × batch-tile inner, dx and dw
    accumulated in-register across the inner dimension, DESIGN.md §9) —
    every paper activation, still a single dx+dw launch."""
    params, x, y = _params_and_batch(LP_ALL, b=160, seed=5)

    def loss(impl):
        return lambda p: fused_loss(p, x, y, LP_ALL, bd_impl=impl)[0]

    ge = jax.grad(loss("einsum"))(params)
    gf = jax.grad(loss("fused"))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6), ge, gf)


def test_grad_matches_large_batch_direct_kernel():
    """B=1024 with block_b=128 (8 inner batch tiles) straight through the
    mid-layer custom-VJP primitive on a ragged MULTI-BUCKET layout: the
    §9 acceptance shape for the two-level grid, per-operand grads vs the
    einsum+activation reference."""
    lp = LayeredPopulation(5, 2, ((11, 3, 5), (4,), (24, 16), (9, 9)),
                           ("gelu", "sigmoid", "tanh", "relu"), block=8)
    params = init_params(jax.random.PRNGKey(7), lp)
    w, bia = params["mid"][0]["w"], params["mid"][0]["b"]
    h = jax.random.normal(jax.random.PRNGKey(8),
                          (1024, lp.layer_pop(0).total_hidden))

    def ref(hh, ww, bb):
        z = block_diag_einsum(hh, ww, lp, 0)
        z = z + bb * jnp.asarray(lp.active_unit_mask(1), jnp.float32)
        return _act(lp, 1, z)

    def fus(hh, ww, bb):
        return block_diag_fused(hh, ww, lp, 0, bias=bb, block_b=128)

    np.testing.assert_allclose(np.asarray(ref(h, w, bia)),
                               np.asarray(fus(h, w, bia)),
                               rtol=1e-5, atol=1e-6)
    ge = jax.grad(lambda *a: (ref(*a) ** 2).sum(), argnums=(0, 1, 2))(
        h, w, bia)
    gf = jax.grad(lambda *a: (fus(*a) ** 2).sum(), argnums=(0, 1, 2))(
        h, w, bia)
    jax.tree.map(
        lambda a_, b_: np.testing.assert_allclose(
            np.asarray(a_), np.asarray(b_), rtol=1e-4, atol=1e-4),
        ge, gf)


def test_bf16_grad_multi_batch_tile():
    """The two-level-grid backward under the bf16 policy at B > block_b:
    bf16 operands, f32 accumulators/grads — fused tracks einsum within
    bf16 tolerance across the batch-tile loop (accumulator dtype bugs
    amplify with more inner steps, so this is where they'd show)."""
    params, x, y = _params_and_batch(LP_ALL, b=160, seed=11)
    ge = jax.grad(lambda p: fused_loss(p, x, y, LP_ALL, bd_impl="einsum",
                                       compute_dtype="bfloat16")[0])(params)
    gf = jax.grad(lambda p: fused_loss(p, x, y, LP_ALL, bd_impl="fused",
                                       compute_dtype="bfloat16")[0])(params)
    for a, b in zip(jax.tree.leaves(ge), jax.tree.leaves(gf)):
        assert b.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-1, atol=5e-2)


@pytest.mark.parametrize("widths,acts,block", [
    (((3,), (5, 2), (9, 7, 4)), ("relu", "hardshrink", "gelu"), 4),
    (((1, 1), (2, 3), (2, 3), (6, 6)), ("selu", "elu", "tanh", "mish"), 8),
    (((11, 3, 5), (4,), (11, 3, 5)), ("gelu", "sigmoid", "leaky_relu"), 2),
])
def test_fused_matches_einsum_ragged_layouts(widths, acts, block):
    """Odd widths / bucket patterns / block sizes, per-mid-layer direct
    call (bias + activation composed manually for the reference)."""
    lp = LayeredPopulation(5, 2, widths, acts, block=block)
    params = init_params(jax.random.PRNGKey(3), lp)
    for l in range(lp.depth - 1):
        w = params["mid"][l]["w"]
        b = params["mid"][l]["b"]
        h = jax.random.normal(jax.random.PRNGKey(10 + l),
                              (7, lp.layer_pop(l).total_hidden))

        def ref(hh, ww, bb):
            z = block_diag_einsum(hh, ww, lp, l)
            z = z + bb * jnp.asarray(lp.active_unit_mask(l + 1), jnp.float32)
            return _act(lp, l + 1, z)

        def fus(hh, ww, bb):
            return block_diag_fused(hh, ww, lp, l, bias=bb)

        ye, yf = ref(h, w, b), fus(h, w, b)
        np.testing.assert_allclose(np.asarray(ye), np.asarray(yf),
                                   rtol=1e-5, atol=1e-6)
        ge = jax.grad(lambda *a: (ref(*a) ** 2).sum(), argnums=(0, 1, 2))(
            h, w, b)
        gf = jax.grad(lambda *a: (fus(*a) ** 2).sum(), argnums=(0, 1, 2))(
            h, w, b)
        jax.tree.map(
            lambda a_, b_: np.testing.assert_allclose(
                np.asarray(a_), np.asarray(b_), rtol=1e-4, atol=1e-5),
            ge, gf)


def test_fused_with_shard_pad_fillers():
    """Filler members (identity activation, trained but excluded from
    selection) ride through the fused kernel exactly like einsum."""
    lp = LayeredPopulation(6, 3, ((5, 3), (12, 9), (7,)),
                           ("relu", "mish", "tanh"), block=8)
    lp_pad = lp.shard_pad(4)
    assert lp_pad.n_pad > 0
    params = pad_params(init_params(jax.random.PRNGKey(0), lp), lp, lp_pad,
                        jax.random.PRNGKey(9))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 3)
    ye = forward(params, x, lp_pad, bd_impl="einsum")
    yf = forward(params, x, lp_pad, bd_impl="fused")
    np.testing.assert_allclose(np.asarray(ye), np.asarray(yf),
                               rtol=1e-5, atol=1e-6)
    ge = jax.grad(lambda p: fused_loss(p, x, y, lp_pad,
                                       bd_impl="einsum")[0])(params)
    gf = jax.grad(lambda p: fused_loss(p, x, y, lp_pad,
                                       bd_impl="fused")[0])(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6), ge, gf)


@pytest.mark.parametrize("bd_impl", BD_CHOICES)
def test_bf16_policy_tracks_f32(bd_impl):
    """bf16 operands / f32 accumulators: both paths' bf16 loss and
    gradients stay within bf16 tolerance of its f32 run, and the f32
    master-parameter update keeps its dtype."""
    params, x, y = _params_and_batch(LP_ALL)
    l32, _ = fused_loss(params, x, y, LP_ALL, bd_impl=bd_impl)
    l16, _ = fused_loss(params, x, y, LP_ALL, bd_impl=bd_impl,
                        compute_dtype="bfloat16")
    assert l16.dtype == jnp.float32          # fp32 loss under the policy
    np.testing.assert_allclose(float(l32), float(l16), rtol=5e-2)

    g32 = jax.grad(lambda p: fused_loss(p, x, y, LP_ALL,
                                        bd_impl=bd_impl)[0])(params)
    g16 = jax.grad(lambda p: fused_loss(p, x, y, LP_ALL, bd_impl=bd_impl,
                                        compute_dtype="bfloat16")[0])(params)
    for a, b in zip(jax.tree.leaves(g32), jax.tree.leaves(g16)):
        assert b.dtype == jnp.float32        # grads land f32 on f32 masters
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-1, atol=5e-2)

    new, _, _ = sgd_step(params, x, y, 0.05, LP_ALL, bd_impl=bd_impl,
                         compute_dtype="bfloat16")
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(new))


def test_bf16_fused_matches_bf16_einsum():
    """The fused kernel's bf16 path agrees with the einsum bf16 path far
    tighter than either agrees with f32 — the epilogue itself adds no
    precision loss beyond the operand cast."""
    params, x, _ = _params_and_batch(LP_ALL)
    ye = forward(params, x, LP_ALL, bd_impl="einsum",
                 compute_dtype="bfloat16")
    yf = forward(params, x, LP_ALL, bd_impl="fused",
                 compute_dtype="bfloat16")
    np.testing.assert_allclose(np.asarray(ye), np.asarray(yf),
                               rtol=2e-2, atol=2e-2)
