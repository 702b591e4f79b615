"""Flash attention (forward) — fused online-softmax attention in VMEM.

WHY (EXPERIMENTS §Perf Cell B diagnosis): the memory term of every
attention-bearing train/prefill cell is dominated by (Sq × block_k) score
tensors round-tripping HBM — XLA materialises each chunk's dot.  This
kernel keeps the whole (scores → mask → online softmax → weighted V)
pipeline in VMEM: HBM sees only Q, K, V once and O once — arithmetic
intensity rises from ~1 to ~d_head FLOP/byte.

TPU mapping:
  grid = (batch·heads, Sq/block_q, Sk/block_k), k-blocks innermost; the
  running (m, l, acc) state lives in VMEM scratch across the k-dimension
  of the grid (the standard Pallas reduction idiom — same trick as the M3
  kernel's output-block accumulation, which is why it lives in this repo).
  GQA without materialised KV repeat: the K/V BlockSpec index_map divides
  the head index by the group size — each q-head group reads its kv head
  straight from HBM.
  Causality + sliding windows are position arithmetic on block offsets;
  scratch rows are (block_q, 128) lane-replicated (TPU VMEM layout).

Backward falls back to the exact chunked-scan XLA path via custom_vjp
(recompute-from-inputs) — flash-bwd is follow-up work; the forward alone
covers serving/prefill and the recompute half of remat'd training.
Validated against kernels/ref.flash_attn_ref + nn/attention.attend_dense in
interpret mode (tests/test_flash_attn.py)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, block_q: int,
            block_k: int, seq_k: int):
    i = pl.program_id(1)                  # q block
    j = pl.program_id(2)                  # k block
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                          # (block_q, dh)
    k = k_ref[0]                          # (block_k, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    ok = k_pos < seq_k                    # kv padding
    if causal:
        ok &= q_pos >= k_pos
    if window > 0:
        ok &= (q_pos - k_pos) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[:, :1]                                    # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                          # (bq, 1)
    p = jnp.exp(s - m_new)                                   # (bq, bk)
    l_new = l_ref[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, scale: float, causal: bool,
                        window: int, block_q: int = 512, block_k: int = 512,
                        interpret: bool = False):
    """q (B,H,Sq,dh), k/v (B,Hkv,Sk,dh) → o (B,H,Sq,dh).

    H must be a multiple of Hkv (GQA groups map through the index_map —
    KV is never repeated in memory)."""
    b, h, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v
    grid = (b * h, (sq + pad_q) // block_q, (sk + pad_k) // block_k)

    kern = functools.partial(
        _kernel, scale=scale, causal=causal,
        window=window if window else 0,
        block_q=block_q, block_k=block_k, seq_k=sk)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh),
                         lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda bh, i, j, g=g, h=h: (
                             (bh % h) // g + (bh // h) * (h // g), j, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda bh, i, j, g=g, h=h: (
                             (bh % h) // g + (bh // h) * (h // g), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq + pad_q, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),   # running denom
            pltpu.VMEM((block_q, dh), jnp.float32),      # output acc
        ],
        interpret=interpret,
        name="flash_attn",
    )(qp.reshape(b * h, sq + pad_q, dh),
      kp.reshape(b * hkv, sk + pad_k, dh),
      vp.reshape(b * hkv, sk + pad_k, dh))
    return out.reshape(b, h, sq + pad_q, dh)[:, :, :sq]
