"""Fused loss head (kernels/loss_head.py, DESIGN.md §9): output projection
(M3) + per-member softmax cross-entropy + dlogits in ONE Pallas pass — the
logits never reach HBM.  Interpret-mode equivalence vs the XLA reference
(m3 + log_softmax) for the per-member losses and the h/W_out/b_out
gradients, including non-uniform per-member cotangents, multi-batch-tile
shapes, and bf16 operands."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.activations import ACTIVATION_ORDER
from repro.core.deep import fused_loss, init_params
from repro.core.m3 import m3, m3_loss_head
from repro.core.population import LayeredPopulation
from repro.kernels import loss_head, tiling
from _kernel_routing import kernel_names

_WIDTHS = ((5, 3), (12, 9), (7,), (17, 9, 5), (8, 8),
           (5, 3), (3, 11, 2), (24, 16), (4,), (9, 9, 9))
LP = LayeredPopulation(6, 3, _WIDTHS, ACTIVATION_ORDER, block=8)
POP = LP.layer_pop(LP.depth - 1)


def _head_inputs(b=9, seed=0):
    params = init_params(jax.random.PRNGKey(seed), LP)
    h = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (b, POP.total_hidden))
    y = jax.random.randint(jax.random.PRNGKey(seed + 2), (b,), 0,
                           LP.out_features)
    return h, params["w_out"], params["b_out"], y


def _per_ref(h, w2, b2, y):
    """The pre-§9 XLA loss head: M3 logits in HBM + log_softmax + NLL."""
    logits = m3(h, w2, POP) + b2
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None, None], axis=2)[:, :, 0]
    return nll.mean(axis=0)


def test_registry():
    """The fused path's loss is the loss-head kernel, both directions, and
    its no-gradient evaluation the eval variant; the einsum path's loss
    (m3 logits + log_softmax) launches no kernel."""
    params = init_params(jax.random.PRNGKey(0), LP)
    x = jax.random.normal(jax.random.PRNGKey(1), (9, LP.in_features))
    y = jnp.zeros((9,), jnp.int32)

    def loss(bd_impl):
        return lambda p: fused_loss(p, x, y, LP, bd_impl=bd_impl)[0]

    assert {"loss_head_fwd", "loss_head_bwd"} <= kernel_names(
        jax.grad(loss("fused")), params)
    assert "loss_head_eval" in kernel_names(loss("fused"), params)
    assert kernel_names(jax.grad(loss("einsum")), params) == set()


def test_per_member_loss_matches_xla():
    h, w2, b2, y = _head_inputs()
    pe = _per_ref(h, w2, b2, y)
    pf = m3_loss_head(h, w2, b2, y, POP)
    assert pf.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(pe), np.asarray(pf),
                               rtol=1e-5, atol=1e-6)


def test_grads_match_xla():
    h, w2, b2, y = _head_inputs(seed=3)
    ge = jax.grad(lambda *a: _per_ref(*a, y).sum(),
                  argnums=(0, 1, 2))(h, w2, b2)
    gf = jax.grad(lambda *a: m3_loss_head(*a, y, POP).sum(),
                  argnums=(0, 1, 2))(h, w2, b2)
    for a, f in zip(ge, gf):
        assert f.shape == a.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(f),
                                   rtol=1e-4, atol=1e-6)


def test_grads_with_per_member_cotangent():
    """A NON-uniform per-member cotangent (the real caller is per.sum(),
    but selection/halving code may weight members): the backward must
    scale each member's dlogits tile by ITS d_per, not a shared scalar."""
    h, w2, b2, y = _head_inputs(seed=5)
    wts = jnp.linspace(0.1, 2.0, POP.num_members)
    ge = jax.grad(lambda *a: (_per_ref(*a, y) * wts).sum(),
                  argnums=(0, 1, 2))(h, w2, b2)
    gf = jax.grad(lambda *a: (m3_loss_head(*a, y, POP) * wts).sum(),
                  argnums=(0, 1, 2))(h, w2, b2)
    for a, f in zip(ge, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(f),
                                   rtol=1e-4, atol=1e-6)


def test_multi_batch_tile():
    """B > block_b (300 → 3 padded batch tiles at block_b=128): per-member
    means and grads stay exact — pad rows carry target −1 and contribute
    zero loss / zero dlogits."""
    h, w2, b2, y = _head_inputs(b=300, seed=7)
    pe = _per_ref(h, w2, b2, y)
    pf = m3_loss_head(h, w2, b2, y, POP)
    np.testing.assert_allclose(np.asarray(pe), np.asarray(pf),
                               rtol=1e-5, atol=1e-6)
    ge = jax.grad(lambda hh: _per_ref(hh, w2, b2, y).sum())(h)
    gf = jax.grad(lambda hh: m3_loss_head(hh, w2, b2, y, POP).sum())(h)
    np.testing.assert_allclose(np.asarray(ge), np.asarray(gf),
                               rtol=1e-4, atol=1e-6)


def test_bf16_operands_f32_loss():
    """bf16 h/W_out tiles: the logits accumulator, softmax math, and the
    per-member losses stay f32; the result tracks the XLA bf16 reference
    within bf16 tolerance."""
    h, w2, b2, y = _head_inputs(seed=9)
    h16, w16 = h.astype(jnp.bfloat16), w2.astype(jnp.bfloat16)
    pe = _per_ref(h16, w16, b2, y)
    pf = m3_loss_head(h16, w16, b2, y, POP)
    assert pf.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(pe, dtype=np.float32),
                               np.asarray(pf), rtol=5e-2, atol=5e-2)


# Re-tiled grid: every step takes the whole batch and ``blocks_per_tile``
# hidden blocks.  Members of 1–3 blocks of 8 lanes (26 blocks, 13 members)
# at 8 blocks per tile put tile boundaries inside members (blocks 7|8,
# 15|16, 23|24) and leave the last tile two blocks full.
_TILED_WIDTHS = ((5,), (12,), (20,), (8,), (17,), (3,), (24,), (9,),
                 (16,), (2,), (23,), (7,), (11,))
_TILE = 8


def _tiled_pop(o):
    acts = tuple(ACTIVATION_ORDER[i % len(ACTIVATION_ORDER)]
                 for i in range(len(_TILED_WIDTHS)))
    lp = LayeredPopulation(6, o, _TILED_WIDTHS, acts, block=8)
    return lp, lp.layer_pop(0)


def test_tiled_layout_crosses_tile_boundaries():
    """The layout the tiled cases below rely on: members span 1–3 blocks,
    some cross a tile boundary, and the last tile is partial."""
    _, pop = _tiled_pop(2)
    seg = np.asarray(pop.block_segment_ids)
    assert set(np.bincount(seg)) == {1, 2, 3}
    crossing = [t for t in range(_TILE, len(seg), _TILE)
                if seg[t - 1] == seg[t]]
    assert crossing
    assert len(seg) % _TILE and pop.num_members % _TILE


def _tiled_inputs(o, b, seed):
    lp, pop = _tiled_pop(o)
    params = init_params(jax.random.PRNGKey(seed), lp)
    h = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (b, pop.total_hidden))
    y = jax.random.randint(jax.random.PRNGKey(seed + 2), (b,), 0, o)
    return pop, h, params["w_out"], params["b_out"], y


def _per_ref_pop(pop, h, w2, b2, y):
    logits = m3(h, w2, pop) + b2
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None, None], axis=2)[:, :, 0]
    return nll.mean(axis=0)


# many classes too (helena's 100, dionis's 355): the kernels take the
# class axis whole, so their code is the same at every class count.  The
# tolerances are those of the two-class cases: the kernels and the XLA head
# both sum in f32, the log-sum-exp over classes in another order, which
# moves a loss near log(O) by a few ulps (rtol 1e-5) and a gradient entry
# by round-off relative to its row (rtol 1e-4, atol 1e-6 for entries that
# cancel to near zero).
_TILED_CASES = ([(o, b) for o in (2, 3) for b in (9, 32, 256)]
                + [(o, b) for o in (10, 100, 355) for b in (9, 256)])


def _tile(monkeypatch, b, g):
    """Steer the head to ``g`` blocks of 8 lanes per grid step at batch b
    (padded to 8 rows) through the bytes a step aims to read."""
    monkeypatch.setattr(tiling, "TILE_BYTES", -(-b // 8) * 8 * 8 * 4 * g)


@pytest.mark.parametrize("o,b", _TILED_CASES)
def test_tiled_per_member_loss(monkeypatch, o, b):
    _tile(monkeypatch, b, _TILE)
    pop, h, w2, b2, y = _tiled_inputs(o, b, seed=11)
    pe = _per_ref_pop(pop, h, w2, b2, y)
    pf = m3_loss_head(h, w2, b2, y, pop)
    np.testing.assert_allclose(np.asarray(pe), np.asarray(pf),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("o,b", _TILED_CASES)
def test_tiled_grads_with_per_member_cotangent(monkeypatch, o, b):
    """h/W_out/b_out gradients under a NON-uniform per-member cotangent:
    the reverse-order backward must carry each member's scaled dlogits
    from its last block back over a tile boundary to its first."""
    _tile(monkeypatch, b, _TILE)
    pop, h, w2, b2, y = _tiled_inputs(o, b, seed=13)
    wts = jnp.linspace(0.1, 2.0, pop.num_members)
    ge = jax.grad(lambda *a: (_per_ref_pop(pop, *a, y) * wts).sum(),
                  argnums=(0, 1, 2))(h, w2, b2)
    gf = jax.grad(lambda *a: (m3_loss_head(*a, y, pop) * wts).sum(),
                  argnums=(0, 1, 2))(h, w2, b2)
    for a, f in zip(ge, gf):
        assert f.shape == a.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(f),
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("tile", [8, 16, 26])
def test_tiling_does_not_change_result(monkeypatch, tile):
    """The same losses and gradients at 8, 16 and every block per tile."""
    pop, h, w2, b2, y = _tiled_inputs(2, 32, seed=17)

    def run(g):
        _tile(monkeypatch, 32, g)
        return jax.value_and_grad(
            lambda hh: m3_loss_head(hh, w2, b2, y, pop).sum())(h)
    (l1, g1), (l2, g2) = run(tile), run(1024)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("o", [2, 3])
def test_recomputed_dlogits_match_stored(monkeypatch, o):
    """At few classes the forward stores the dlogits; the backward that
    recomputes them from the carries (the many-class path) gives the same
    gradients, members crossing tile boundaries included."""
    _tile(monkeypatch, 32, _TILE)
    pop, h, w2, b2, y = _tiled_inputs(o, 32, seed=23)
    wts = jnp.linspace(0.1, 2.0, pop.num_members)

    def grads():
        return jax.grad(lambda *a: (m3_loss_head(*a, y, pop) * wts).sum(),
                        argnums=(0, 1, 2))(h, w2, b2)
    assert loss_head.stores_dlogits(o)
    stored = grads()
    monkeypatch.setattr(loss_head, "STORE_MAX_CLASSES", 0)
    for a, f in zip(stored, grads()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(f),
                                   rtol=1e-6, atol=1e-8)


def test_member_tables_span_slabs():
    """310 blocks at 136 a tile: ten two-block members first, so the second
    tile ends members 126..261 and writes three 128-member slabs of a
    member table; the last tile holds 38 blocks and clamps its slabs at
    the table's end.  Every member's row is the one its last block wrote."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, g, k = 300, 136, 3
    seg = np.repeat(np.arange(n), [2] * 10 + [1] * (n - 10))
    nt, seg_t = loss_head._tiles(jnp.asarray(seg), g)
    assert len(seg) == 310 and nt == 3
    assert seg[g] == 126 and seg[2 * g - 1] == 261
    tab_shape, tab_spec = loss_head._member_table(n, k)

    def kernel(seg_ref, tab_ref):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _zero():
            tab_ref[...] = jnp.zeros_like(tab_ref)
        # block j's value: its member's id + 1 + row, on K rows
        rows = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)
        vals = [(seg_ref[t * g + j + 1] + 1 + rows).astype(jnp.float32)
                for j in range(g)]
        loss_head._put_members(tab_ref, seg_ref, t, g, vals)

    tab = pl.pallas_call(
        kernel, grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nt,), in_specs=[],
            out_specs=tab_spec),
        out_shape=jax.ShapeDtypeStruct(tab_shape, jnp.float32),
        interpret=True)(seg_t)
    want = np.arange(1, n + 1)[:, None] + np.arange(k)[None]
    np.testing.assert_array_equal(np.asarray(loss_head._members(tab, n)),
                                  want)


@pytest.mark.parametrize("n_blocks,batch,want", [
    (10_000, 256, 16),      # paper-40k, one chip: 2 MiB of h a step
    (1_792, 256, 16),       # deep-1k's last layer
    (10_000, 32, 128),      # a smaller batch takes more blocks
    (10_000, 8, 512),
    (100, 8, 100),          # one tile holds every block
    (5_000, 4096, 8),       # never fewer than the 8-row dlogits tile
])
def test_blocks_per_tile(n_blocks, batch, want):
    assert loss_head.blocks_per_tile(n_blocks, batch, 128, 4) == want


_SHARDED_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import deep
from repro.core.population import LayeredPopulation
from repro.distributed.sharding import population_shardings
from repro.kernels import tiling
from repro.launch.mesh import make_mesh

B = 16
# 8 blocks of 8 lanes per grid step at B = 16, so each shard's head runs
# several tiles with members crossing their boundaries
tiling.TILE_BYTES = B * 8 * 4 * 8
# four equal quarters, so each shard owns whole members' blocks
widths = tuple((w,) for w in (3, 12, 20, 7, 17, 24, 5, 9, 14, 2, 23, 11) * 4)
acts = tuple(("relu", "tanh", "gelu")[i % 3] for i in range(len(widths)))
lp = LayeredPopulation(6, 2, widths, acts, block=8).shard_pad(4)
seg = np.asarray(lp.layer_pop(0).block_segment_ids)
assert deep._member_sharded_unsupported(lp, 4) is None
assert len(seg) // 4 > 16, len(seg)
params = deep.init_params(jax.random.PRNGKey(0), lp)
x = jax.random.normal(jax.random.PRNGKey(1), (B, 6))
y = jax.random.randint(jax.random.PRNGKey(2), (B,), 0, 2)
wts = jnp.linspace(0.5, 1.5, lp.num_members)

def loss(p):
    per = deep.fused_loss(p, x, y, lp, bd_impl="fused")[1]
    return (per * wts).sum(), per

(l1, per1), g1 = jax.value_and_grad(loss, has_aux=True)(params)
mesh = make_mesh((1, 4), ("data", "model"))
with jax.set_mesh(mesh):
    ps = jax.device_put(params, population_shardings(lp, mesh))
    (l4, per4), g4 = jax.jit(jax.value_and_grad(loss, has_aux=True))(ps)
np.testing.assert_allclose(np.asarray(per4), np.asarray(per1),
                           rtol=1e-5, atol=1e-6)
jax.tree.map(lambda a, b: np.testing.assert_allclose(
    np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6), g4, g1)
print("OK", len(seg))
"""


def test_member_sharded_head_matches_one_device():
    """The member-sharded fused loss (``deep._fused_loss_member_sharded``:
    the head under ``shard_map``, 4 virtual CPU devices, a ``shard_pad``ded
    paper-like layout) against one device: per-member losses and every
    gradient, with each shard's head running several tiles."""
    import os
    import pathlib
    import subprocess
    import sys
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src")] + env.get("PYTHONPATH", "").split(os.pathsep))
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "OK" in r.stdout


def _kernel_eqns(o: int) -> dict:
    """Equations in each head kernel's traced body (sub-jaxprs included),
    forward, backward and eval, at O classes on the tiled layout."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    pop, h, w2, b2, y = _tiled_inputs(o, 32, seed=19)

    def count(jaxpr) -> int:
        n = 0
        for e in jaxpr.eqns:
            n += 1
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    if isinstance(sub, ClosedJaxpr):
                        n += count(sub.jaxpr)
                    elif isinstance(sub, Jaxpr):
                        n += count(sub)
        return n

    def kernels(jaxpr, out):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                out[str(e.params["name"])] = count(
                    e.params["jaxpr"])
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    if isinstance(sub, ClosedJaxpr):
                        kernels(sub.jaxpr, out)
                    elif isinstance(sub, Jaxpr):
                        kernels(sub, out)
        return out

    def train(hh, ww, bb):
        return jax.value_and_grad(
            lambda *a: m3_loss_head(*a, y, pop).sum(),
            argnums=(0, 1, 2))(hh, ww, bb)

    out = kernels(jax.make_jaxpr(train)(h, w2, b2).jaxpr, {})
    out.update(kernels(jax.make_jaxpr(
        lambda *a: m3_loss_head(*a, y, pop))(h, w2, b2).jaxpr, {}))
    return out


def test_kernel_code_does_not_grow_with_classes(monkeypatch):
    """No Python loop runs over the classes in the many-class body: each
    head kernel's traced body holds as many equations at 355 classes as
    at 9, the fewest it serves, and as at 2 when it is made to serve
    them.  Up to ``STORE_MAX_CLASSES`` the few-class body, which unrolls
    over the classes, serves instead."""
    nine, many = _kernel_eqns(9), _kernel_eqns(355)
    assert set(nine) == {"loss_head_many_fwd", "loss_head_many_bwd",
                         "loss_head_many_eval"}
    assert nine == many
    assert set(_kernel_eqns(loss_head.STORE_MAX_CLASSES)) == {
        "loss_head_fwd", "loss_head_bwd", "loss_head_eval"}
    monkeypatch.setattr(loss_head, "STORE_MAX_CLASSES", 0)
    assert _kernel_eqns(2) == many
