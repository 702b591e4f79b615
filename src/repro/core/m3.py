"""Modified Matrix Multiplication (M3) — the paper's core operation.

Computes, for a fused hidden tensor ``h`` (batch, total_hidden) and a fused
output weight ``w2`` (out, total_hidden) with per-unit member ids ``seg``:

    y[b, m, o] = sum_{j : seg[j] == m}  h[b, j] * w2[o, j]

i.e. a matmul whose reduction is *segmented* by member, so each member's
output (and therefore gradient) is computed from its own hidden slice only.

Four implementations, identical semantics (cross-checked in tests):

  m3_scatter   — paper-faithful GPU formulation: broadcast element-wise
                 product + scatter-add (jax.ops.segment_sum).  Materialises
                 the (B, O, H) intermediate; memory-bound.  This is the
                 *reproduction baseline* recorded in EXPERIMENTS.md.
  m3_onehot    — single einsum against a one-hot segment selector; dense and
                 MXU-friendly but does P× redundant compute.  Included for the
                 shoot-out benchmark.
  m3_bucketed  — members bucketed by padded hidden size → per-bucket batched
                 matmul ('bnh,noh->bno').  Dense, zero scatter, XLA-native;
                 the best non-Pallas TPU formulation.
  m3_pallas    — segment-blocked matmul Pallas kernel (kernels/m3_matmul.py):
                 one dense (Bt×k)·(k×O) MXU matmul per hidden tile accumulated
                 in VMEM into the output block chosen by a scalar-prefetched
                 segment id.  TPU-native adaptation (DESIGN.md §2).

All take the static ``Population`` layout for segment metadata and an optional
``precision``.  Shapes: h (B, H), w2 (O, H) → y (B, P, O).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.population import Population


# ---------------------------------------------------------------------- #
# 1. paper-faithful: broadcast multiply + scatter-add                     #
# ---------------------------------------------------------------------- #

def m3_scatter(h: jax.Array, w2: jax.Array, pop: Population) -> jax.Array:
    """The paper's M3: S[b,o,j] = h[b,j]·w2[o,j]; scatter-add over j by member.

    ``jax.ops.segment_sum`` reduces over the *leading* axis, so we transpose the
    broadcast product to (H, B, O).  num_segments is static → jit-safe.
    """
    s = h[:, None, :] * w2[None, :, :]            # (B, O, H)  — the paper's S
    if s.dtype != jnp.float32:                    # bf16 operands: f32 reduce
        s = s.astype(jnp.float32)
    s = jnp.moveaxis(s, -1, 0)                     # (H, B, O)
    y = jax.ops.segment_sum(
        s, jnp.asarray(pop.segment_ids),
        num_segments=pop.num_members,
        indices_are_sorted=True)                   # (P, B, O)
    return jnp.moveaxis(y, 0, 1)                   # (B, P, O)


# ---------------------------------------------------------------------- #
# 2. one-hot einsum                                                      #
# ---------------------------------------------------------------------- #

def m3_onehot(h: jax.Array, w2: jax.Array, pop: Population) -> jax.Array:
    sel = jax.nn.one_hot(jnp.asarray(pop.segment_ids), pop.num_members,
                         dtype=h.dtype)            # (H, P)
    # y[b,m,o] = sum_j h[b,j] w2[o,j] sel[j,m]
    return jnp.einsum("bj,oj,jm->bmo", h, w2, sel,
                      optimize="greedy",
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------- #
# 3. bucketed batched matmul                                             #
# ---------------------------------------------------------------------- #

def _buckets(pop: Population):
    """Contiguous runs of members with identical *padded* size — now owned by
    the layout primitive itself (``Population.size_buckets``); kept as an
    alias for callers of the original private helper."""
    return pop.size_buckets()


def m3_bucketed(h: jax.Array, w2: jax.Array, pop: Population) -> jax.Array:
    """Reshape each equal-size run of members to (B, n, hs) and batched-matmul
    against (n, O, hs).  Pure dense compute; padding columns multiply zeros."""
    b = h.shape[0]
    o = w2.shape[0]
    pieces = []
    for (m0, n, hs, col0) in _buckets(pop):
        hh = h[:, col0: col0 + n * hs].reshape(b, n, hs)
        ww = w2[:, col0: col0 + n * hs].reshape(o, n, hs)
        pieces.append(jnp.einsum("bnh,onh->bno", hh, ww,
                                 preferred_element_type=jnp.float32))
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)


# ---------------------------------------------------------------------- #
# 4. Pallas segment-blocked matmul                                       #
# ---------------------------------------------------------------------- #

def m3_pallas(h: jax.Array, w2: jax.Array, pop: Population, *,
              interpret: bool | None = None, block_b: int = 128) -> jax.Array:
    from repro.kernels.ops import m3_matmul  # lazy: kernels import pallas
    return m3_matmul(h, w2,
                     block_seg_ids=np.asarray(pop.block_segment_ids),
                     num_members=pop.num_members,
                     block_h=pop.block, block_b=block_b,
                     interpret=interpret)


M3_IMPLS = {
    "scatter": m3_scatter,
    "onehot": m3_onehot,
    "bucketed": m3_bucketed,
    "pallas": m3_pallas,
}


def m3(h: jax.Array, w2: jax.Array, pop: Population,
       impl: str = "bucketed", **kw) -> jax.Array:
    return M3_IMPLS[impl](h, w2, pop, **kw)


# ---------------------------------------------------------------------- #
# 5. fused loss head: M3 projection + softmax-XE in one pass             #
# ---------------------------------------------------------------------- #

def m3_loss_head(h: jax.Array, w2: jax.Array, b2: jax.Array,
                 targets: jax.Array, pop: Population, *,
                 interpret: bool | None = None) -> jax.Array:
    """The training-time fusion of M3: projection + per-member bias +
    softmax cross-entropy + dlogits in one Pallas launch per direction
    (kernels/loss_head.py, DESIGN.md §9) — the logits never reach HBM.
    Returns the per-member mean NLL (P,) f32; paths that need actual
    logits use ``m3`` (training) or ``m3_infer_head`` (serving)."""
    from repro.kernels.ops import loss_head  # lazy: kernels import pallas
    return loss_head(h, w2, b2, targets,
                     np.asarray(pop.block_segment_ids),
                     block_h=pop.block, interpret=interpret)


# loss-head impls that bypass logits materialisation entirely; the name
# mirrors FUSED_BD_IMPLS — deep.fused_loss routes through this registry
LOSS_IMPLS = {
    "xla": None,          # log_softmax over forward() logits (deep.fused_loss)
    "fused": m3_loss_head,
}
FUSED_LOSS_IMPLS = frozenset(["fused"])


# ---------------------------------------------------------------------- #
# 6. forward-only inference head: M3 + bias (+ log-softmax) in one pass  #
# ---------------------------------------------------------------------- #

def m3_infer_head(h: jax.Array, w2: jax.Array, b2: jax.Array,
                  pop: Population, *, log_probs: bool = False,
                  interpret: bool | None = None,
                  block_b: int | None = None) -> jax.Array:
    """The serving-time counterpart of ``m3_loss_head``: projection +
    per-member bias — and optionally the stable log-softmax — in ONE
    forward-only Pallas launch (kernels/infer_head.py, DESIGN.md §10),
    producing the (B, P, O) logits/log-probs the ensemble reductions
    consume.  Not differentiable: the inference hot path must not be able
    to emit residuals.  This retires the old caveat that eval paths
    needing actual logits fall back to ``m3`` + XLA bias/softmax."""
    from repro.kernels.ops import INFER_BLOCK_B, infer_head  # lazy
    return infer_head(h, w2, b2, np.asarray(pop.block_segment_ids),
                      block_h=pop.block,
                      block_b=INFER_BLOCK_B if block_b is None else block_b,
                      log_probs=log_probs, interpret=interpret)


def m3_infer_head_int8(h: jax.Array, w2_q: jax.Array, w2_scale: jax.Array,
                       b2: jax.Array, pop: Population, *,
                       log_probs: bool = False,
                       interpret: bool | None = None,
                       block_b: int | None = None) -> jax.Array:
    """``m3_infer_head`` over the int8 serve copy (DESIGN.md §12): the
    head weight stays int8 in HBM, one f32 scale per hidden tile is
    dequantized inside the projection loop."""
    from repro.kernels.ops import INFER_BLOCK_B, infer_head_int8  # lazy
    return infer_head_int8(
        h, w2_q, w2_scale, b2, np.asarray(pop.block_segment_ids),
        block_h=pop.block,
        block_b=INFER_BLOCK_B if block_b is None else block_b,
        log_probs=log_probs, interpret=interpret)


# inference head impls — deep.forward(infer=True) routes through this
HEAD_IMPLS = {
    "xla": None,          # m3 logits + XLA bias/log_softmax (deep.forward)
    "fused": m3_infer_head,
    "fused_int8": m3_infer_head_int8,   # int8 serve copy (weights_dtype)
}
