"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp
oracles (kernels/ref.py, brute-force loops, the masked activation select),
swept over shapes and dtypes, values + grads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.activations import ACTIVATION_ORDER, apply_activations_masked
from repro.kernels import moe_gemm, ref
from repro.kernels.ops import fused_input, loss_head


def _seg_layout(rng, n_members, blocks_per=3, block_h=8):
    """Random contiguous per-block member ids (sorted)."""
    counts = rng.integers(1, blocks_per + 1, n_members)
    ids = np.repeat(np.arange(n_members, dtype=np.int32), counts)
    return ids, int(ids.size * block_h)


def _head_brute_force(h, w2, b2, y, unit_ids, members):
    """Per-member mean NLL by the obvious loop: member m's logits from its
    own hidden columns only, then ``log_softmax``."""
    per = []
    for m in range(members):
        cols = np.flatnonzero(unit_ids == m)
        logits = h[:, cols] @ w2[:, cols].T + b2[m]
        logp = jax.nn.log_softmax(logits, axis=-1)
        per.append(-jnp.take_along_axis(logp, y[:, None], axis=1).mean())
    return jnp.stack(per)


def _head_inputs(rng, b, o, members, block_h, dtype):
    ids, hh = _seg_layout(rng, members, block_h=block_h)
    h = jnp.asarray(rng.normal(0, 1, (b, hh)), dtype)
    w2 = jnp.asarray(rng.normal(0, 0.5, (o, hh)), dtype)
    b2 = jnp.asarray(rng.normal(0, 1, (members, o)), jnp.float32)
    y = jnp.asarray(rng.integers(0, o, b), jnp.int32)
    return ids, h, w2, b2, y


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,o,members,block_h", [
    (4, 3, 2, 8), (16, 2, 5, 8), (7, 9, 3, 16), (1, 2, 1, 8), (32, 4, 7, 8),
])
def test_loss_head_matches_brute_force(b, o, members, block_h, dtype, rng):
    """The fused loss head against the per-member loop: B = 1 and 7 pad
    the batch, O = 9 takes the many-class body."""
    ids, h, w2, b2, y = _head_inputs(rng, b, o, members, block_h, dtype)
    got = loss_head(h, w2, b2, y, ids, block_h=block_h, interpret=True)
    want = _head_brute_force(h.astype(jnp.float32), w2.astype(jnp.float32),
                             b2, y, np.repeat(ids, block_h), members)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_loss_head_grads_match_brute_force(rng):
    ids, h, w2, b2, y = _head_inputs(rng, 7, 9, 3, 16, jnp.float32)
    units = np.repeat(ids, 16)

    def loss_k(hh, ww, bb):
        return (loss_head(hh, ww, bb, y, ids, block_h=16, interpret=True)
                * jnp.arange(1.0, 4.0)).sum()

    def loss_r(hh, ww, bb):
        return (_head_brute_force(hh, ww, bb, y, units, 3)
                * jnp.arange(1.0, 4.0)).sum()

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(h, w2, b2)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(h, w2, b2)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,blocks,block_h", [(4, 3, 8), (9, 10, 8), (2, 4, 16)])
def test_fused_input_epilogue_matches_masked(b, blocks, block_h, dtype, rng):
    """The fused input layer's activation epilogue against the branchless
    oracle, a different activation in every block and a random mask."""
    ids = rng.permutation(len(ACTIVATION_ORDER))[:blocks].astype(np.int32)
    hh, f = blocks * block_h, 5
    mask = (rng.random(hh) > 0.2).astype(np.float32)
    x = jnp.asarray(rng.normal(0, 1, (b, f)), dtype)
    w = jnp.asarray(rng.normal(0, 1, (hh, f)), dtype)
    bias = jnp.asarray(rng.normal(0, 1, hh), jnp.float32)
    got = fused_input(x, w, bias, ids, mask, block=block_h, interpret=True)
    z = x.astype(jnp.float32) @ w.astype(jnp.float32).T + bias
    want = (apply_activations_masked(z, np.repeat(ids, block_h)) * mask)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want.astype(dtype), np.float32),
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
