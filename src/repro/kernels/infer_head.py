"""Forward-only inference head: output projection (M3) + per-member bias
(+ optional log-softmax) in ONE Pallas pass (DESIGN.md §10).

Derived from the loss-head kernel (kernels/loss_head.py) by keeping its
projection loop and REPLACING the epilogue: no targets, no NLL, no
dlogits_base — the epilogue just adds the member bias to the still-in-VMEM
f32 accumulator and stores the finished (block_b, O) logits tile straight
into its member's slot of the member-major (P, B, O) output (member axis
squeezed out of the block, so the block's last two dims are (block_b, O) —
the TPU (8, 128) tiling rule; the bias is read the same way from a
(P, 1, O) view).  With ``log_probs=True``
the same stable logsumexp the loss head runs produces normalised
log-probabilities instead — serving's soft-vote ensembles consume
``exp(log_probs)`` without any extra XLA softmax pass over the (B, P, O)
tensor.

What the epilogue DROPS vs training (and why the batch tile can grow):
the loss head keeps a second (block_b, O) array live for dlogits_base and
the per-member (1, P) loss scratch; the mid/input training kernels keep a
whole (block_b, H_out) g' residual block.  Here the only live buffers are
the h/w tiles and ONE f32 accumulator, so ``block_b`` defaults to 2× the
training tile (kernels/ops.py routes 256 vs 128) and the grid has half
the batch rows.

Grid/tile metadata is the per-block member id (``block_segment_ids``)
scalar-prefetched exactly like the loss head: member boundaries
(first/last) come from neighbouring ids, so ragged member widths need no
extra metadata.  O pads via −1e30 bias columns (zero softmax mass under
``log_probs``; the caller slices them off regardless).

Mixed precision: h/w tiles may be bf16; the accumulator and the emitted
logits / log-probs are always f32.

There is NO backward: this kernel exists so that no VJP (and no residual)
can even trace into a serving program — training paths keep using the
loss head / m3.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import tpu_compiler_params


def _make_kernel(log_probs: bool):
    def kernel(seg_ref, h_ref, w_ref, b_ref, y_ref, acc_ref):
        t = pl.program_id(1)
        nt = pl.num_programs(1)
        seg_t = seg_ref[t]
        first = jnp.logical_or(t == 0, seg_ref[jnp.maximum(t - 1, 0)] != seg_t)
        last = jnp.logical_or(t == nt - 1,
                              seg_ref[jnp.minimum(t + 1, nt - 1)] != seg_t)

        @pl.when(first)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            h_ref[...], w_ref[...],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _epilogue():
            logits = acc_ref[...] + b_ref[...].astype(jnp.float32)
            if log_probs:
                mx = jnp.max(logits, axis=1, keepdims=True)
                lse = jnp.log(jnp.sum(jnp.exp(logits - mx), axis=1,
                                      keepdims=True)) + mx
                logits = logits - lse
            y_ref[...] = logits
    return kernel


def infer_head_fwd(h: jax.Array, w2: jax.Array, b2: jax.Array,
                   seg: jax.Array, num_members: int, *, block_h: int,
                   block_b: int, log_probs: bool,
                   interpret: bool = False) -> jax.Array:
    """h (B, H), w2 (O, H), b2 (P, O) → logits (or log-probs) (P, B, O)
    f32, member-major.  Forward-only: one launch, no residual outputs."""
    b, hh = h.shape
    o = w2.shape[0]
    p = num_members
    grid = (b // block_b, hh // block_h)
    return pl.pallas_call(
        _make_kernel(log_probs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, block_h),
                             lambda i, t, seg_r: (i, t)),
                pl.BlockSpec((o, block_h), lambda i, t, seg_r: (0, t)),
                pl.BlockSpec((None, 1, o),
                             lambda i, t, seg_r: (seg_r[t], 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, block_b, o),
                                   lambda i, t, seg_r: (seg_r[t], i, 0)),
            scratch_shapes=[pltpu.VMEM((block_b, o), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((p, b, o), jnp.float32),
        compiler_params=tpu_compiler_params(
            ("arbitrary", "arbitrary"),
            (block_b, block_h), (o, block_h), (1, o),
            (block_b, o), (block_b, o)),
        interpret=interpret,
        name="infer_head",
    )(seg, h, w2, b2.reshape(p, 1, o))


# --------------------------------------------------------------------- #
# int8 weights: in-loop dequant + projection + bias (+ log-softmax)     #
# --------------------------------------------------------------------- #

def _make_int8_kernel(log_probs: bool):
    """Int8-weight twin of ``_make_kernel`` (DESIGN.md §12): the hidden
    tile's f32 scale (one per hidden tile — each owned by exactly one
    member's output rows) rides the scalar-prefetch stream next to ``seg``
    (indexed ``sc_ref[t]``, no per-step blocked operand); the int8 weight
    stripe is dequantized on the VPU before the MXU contraction.  Same
    grid, same member-boundary epilogue."""
    def kernel(seg_ref, sc_ref, h_ref, w_ref, b_ref, y_ref, acc_ref):
        t = pl.program_id(1)
        nt = pl.num_programs(1)
        seg_t = seg_ref[t]
        first = jnp.logical_or(t == 0, seg_ref[jnp.maximum(t - 1, 0)] != seg_t)
        last = jnp.logical_or(t == nt - 1,
                              seg_ref[jnp.minimum(t + 1, nt - 1)] != seg_t)

        @pl.when(first)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        w = w_ref[...].astype(jnp.float32) * sc_ref[t]
        acc_ref[...] += jax.lax.dot_general(
            h_ref[...].astype(jnp.float32), w,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _epilogue():
            logits = acc_ref[...] + b_ref[...].astype(jnp.float32)
            if log_probs:
                mx = jnp.max(logits, axis=1, keepdims=True)
                lse = jnp.log(jnp.sum(jnp.exp(logits - mx), axis=1,
                                      keepdims=True)) + mx
                logits = logits - lse
            y_ref[...] = logits
    return kernel


def infer_head_int8_fwd(h: jax.Array, w2_q: jax.Array, w2_scale: jax.Array,
                        b2: jax.Array, seg: jax.Array, num_members: int, *,
                        block_h: int, block_b: int, log_probs: bool,
                        interpret: bool = False) -> jax.Array:
    """h (B, H), w2_q (O, H) int8, w2_scale (H/block_h,) f32
    scalar-prefetch, b2 (P, O) → logits (or log-probs) (P, B, O) f32,
    member-major.  Forward-only, one launch."""
    b, hh = h.shape
    o = w2_q.shape[0]
    p = num_members
    grid = (b // block_b, hh // block_h)
    return pl.pallas_call(
        _make_int8_kernel(log_probs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, block_h),
                             lambda i, t, seg_r, sc: (i, t)),
                pl.BlockSpec((o, block_h), lambda i, t, seg_r, sc: (0, t)),
                pl.BlockSpec((None, 1, o),
                             lambda i, t, seg_r, sc: (seg_r[t], 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, block_b, o),
                                   lambda i, t, seg_r, sc: (seg_r[t], i, 0)),
            scratch_shapes=[pltpu.VMEM((block_b, o), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((p, b, o), jnp.float32),
        compiler_params=tpu_compiler_params(
            ("arbitrary", "arbitrary"),
            (block_b, block_h), (o, block_h), (1, o),
            (block_b, o), (block_b, o)),
        interpret=interpret,
        name="infer_head_int8",
    )(seg, w2_scale, h, w2_q, b2.reshape(p, 1, o))
