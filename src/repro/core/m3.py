"""Modified Matrix Multiplication (M3) — the paper's core operation.

Computes, for a fused hidden tensor ``h`` (batch, total_hidden) and a fused
output weight ``w2`` (out, total_hidden) with per-unit member ids ``seg``:

    y[b, m, o] = sum_{j : seg[j] == m}  h[b, j] * w2[o, j]

i.e. a matmul whose reduction is *segmented* by member, so each member's
output (and therefore gradient) is computed from its own hidden slice only.

Two routes, chosen by the engine's one implementation decision
(``core.deep``'s ``bd_impl``):

  m3              — the XLA route (``bd_impl="einsum"``): members bucketed
                    by padded hidden size, one batched matmul per bucket
                    ('bnh,onh->bno').  Dense, zero scatter; the logits are
                    materialised and the loss is XLA's ``log_softmax``.
  m3_loss_head    — the fused route (``bd_impl="fused"``): projection + bias
                    + softmax cross-entropy + dlogits in one Pallas launch
                    per direction, logits never in HBM; ``m3_infer_head``
                    (and its int8 twin) is its forward-only serving form.

All take the static ``Population`` layout for segment metadata.
Shapes: h (B, H), w2 (O, H) → y (B, P, O).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.population import Population


def m3(h: jax.Array, w2: jax.Array, pop: Population) -> jax.Array:
    """Reshape each equal-size run of members to (B, n, hs) and batched-matmul
    against (n, O, hs), accumulating in f32.  Pure dense compute; padding
    columns multiply zeros."""
    b = h.shape[0]
    o = w2.shape[0]
    pieces = []
    for (m0, n, hs, col0) in pop.size_buckets():
        hh = h[:, col0: col0 + n * hs].reshape(b, n, hs)
        ww = w2[:, col0: col0 + n * hs].reshape(o, n, hs)
        pieces.append(jnp.einsum("bnh,onh->bno", hh, ww,
                                 preferred_element_type=jnp.float32))
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)


# ---------------------------------------------------------------------- #
# fused loss head: M3 projection + softmax-XE in one pass                #
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


# ---------------------------------------------------------------------- #
# forward-only inference head: M3 + bias (+ log-softmax) in one pass     #
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
    to emit residuals."""
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
