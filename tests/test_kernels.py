"""Per-kernel validation: Pallas (interpret=True on CPU) vs the pure-jnp
oracles in kernels/ref.py, swept over shapes and dtypes, values + grads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import m3_matmul, moe_gemm, seg_act
from repro.kernels import ref


def _seg_layout(rng, n_members, blocks_per=3, block_h=8):
    """Random contiguous per-block member ids (sorted)."""
    counts = rng.integers(1, blocks_per + 1, n_members)
    ids = np.repeat(np.arange(n_members, dtype=np.int32), counts)
    return ids, int(ids.size * block_h)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,o,members,block_h", [
    (4, 3, 2, 8), (16, 2, 5, 8), (7, 9, 3, 16), (1, 1, 1, 8), (32, 4, 7, 8),
])
def test_m3_matmul_kernel(b, o, members, block_h, dtype, rng):
    ids, hh = _seg_layout(rng, members, block_h=block_h)
    h = jnp.asarray(rng.normal(0, 1, (b, hh)), dtype)
    w2 = jnp.asarray(rng.normal(0, 1, (o, hh)), dtype)
    got = m3_matmul(h, w2, ids, members, block_h=block_h, interpret=True)
    want = ref.m3_matmul_ref(h, w2, ids, members, block_h)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_m3_matmul_kernel_grads(rng):
    ids, hh = _seg_layout(rng, 4, block_h=8)
    h = jnp.asarray(rng.normal(0, 1, (8, hh)), jnp.float32)
    w2 = jnp.asarray(rng.normal(0, 1, (3, hh)), jnp.float32)

    def loss_k(hh_, ww):
        return (m3_matmul(hh_, ww, ids, 4, block_h=8, interpret=True) ** 2) \
            .sum()

    def loss_r(hh_, ww):
        return (ref.m3_matmul_ref_f32out(hh_, ww, ids, 4, 8) ** 2).sum()

    gk = jax.grad(loss_k, argnums=(0, 1))(h, w2)
    gr = jax.grad(loss_r, argnums=(0, 1))(h, w2)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,blocks,block_h", [(4, 3, 8), (9, 10, 8), (2, 4, 16)])
def test_seg_act_kernel(b, blocks, block_h, dtype, rng):
    ids = jnp.asarray(rng.integers(0, 10, blocks), jnp.int32)
    hh = blocks * block_h
    mask = (rng.random(hh) > 0.2).astype(np.float32)
    h = jnp.asarray(rng.normal(0, 1, (b, hh)), dtype)
    got = seg_act(h, np.asarray(ids), mask, block_h=block_h, interpret=True)
    want = ref.seg_act_ref(h, np.asarray(ids), block_h, mask)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,d,f,block_t", [(2, 16, 24, 8), (4, 32, 16, 8),
                                           (1, 8, 8, 8)])
def test_moe_gemm_kernel(e, d, f, block_t, dtype, rng):
    # tokens sorted by expert, each expert's run a multiple of block_t
    runs = rng.integers(1, 4, e)
    eids = np.repeat(np.arange(e, dtype=np.int32), runs)
    t = int(eids.size) * block_t
    x = jnp.asarray(rng.normal(0, 1, (t, d)), dtype)
    w = jnp.asarray(rng.normal(0, 1, (e, d, f)), dtype)
    got = moe_gemm(x, w, eids, block_t=block_t, block_d=max(d // 2, 8),
                   block_f=max(f // 2, 8), interpret=True)
    want = ref.moe_gemm_ref(x, w, eids, block_t)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_m3_kernel_used_by_population():
    """End-to-end: the Pallas path through the ParallelMLP forward."""
    from repro.core import Population, forward, init_params
    pop = Population(5, 3, (3, 9, 17), ("relu", "tanh", "gelu"), block=8)
    params = init_params(jax.random.PRNGKey(0), pop)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 5))
    y_pallas = forward(params, x, pop, m3_impl="pallas")
    y_ref = forward(params, x, pop, m3_impl="scatter")
    np.testing.assert_allclose(np.asarray(y_pallas), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


def test_interpret_only_on_cpu(monkeypatch):
    """``interpret=None`` interprets on the CPU backend, compiles on a TPU,
    and refuses any other backend instead of interpreting on it."""
    from repro.kernels import ops
    assert ops._resolve_interpret(None) is True
    assert ops._resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._resolve_interpret(None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops._resolve_interpret(None)
