"""Pure-jnp oracles for every Pallas kernel in this package.

These define the *semantics* of the attention and grouped-GEMM kernels; the
kernels must match them (asserted across a shape/dtype sweep in
tests/test_kernels.py).  The population kernels' oracles are the XLA paths
of ``core.deep`` and ``core.activations.apply_activations_masked``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def expand_block_ids(block_ids: np.ndarray, block: int) -> np.ndarray:
    """Per-block id array -> per-unit id array."""
    return np.repeat(np.asarray(block_ids), block)


def flash_attn_ref(q, k, v, *, scale: float, causal: bool, window: int):
    """Dense masked softmax attention. q (B,H,Sq,dh), k/v (B,Hkv,Sk,dh)."""
    b, h, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = h // hkv
    kr = jnp.repeat(k, g, axis=1)
    vr = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(sk)[None, :]
    ok = jnp.ones((sq, sk), bool)
    if causal:
        ok &= qp >= kp
    if window and window > 0:
        ok &= (qp - kp) < window
    s = jnp.where(ok[None, None], s, -1e30)
    w_ = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w_,
                      vr.astype(jnp.float32)).astype(q.dtype)


def moe_gemm_ref(x: jax.Array, w: jax.Array, block_expert_ids: np.ndarray,
                 block_t: int) -> jax.Array:
    """Grouped GEMM: y[t] = x[t] @ w[e(t)].

    x (T, D) tokens sorted by expert (padded so each expert's run is a
    multiple of block_t); w (E, D, F) -> y (T, F)."""
    eid = jnp.asarray(expand_block_ids(block_expert_ids, block_t))
    wt = w[eid]                                   # (T, D, F) gather — oracle only
    return jnp.einsum("td,tdf->tf", x.astype(jnp.float32),
                      wt.astype(jnp.float32)).astype(x.dtype)
