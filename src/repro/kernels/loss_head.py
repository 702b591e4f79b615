"""Fused loss-head kernel: output projection (M3) + softmax cross-entropy
+ dlogits in ONE Pallas pass (DESIGN.md §9).

The pre-§9 loss head ran the M3 segment-blocked matmul, materialised the
(B, P, O) logits in HBM, and let XLA run log_softmax + NLL over them — and
the backward re-materialised dlogits before the M3 transposed kernels.
Here the softmax cross-entropy runs in the epilogue of the projection
while each member's logits tile is still in VMEM:

  forward   per[m] = mean_b( lse(z_m) − z_m[target] )   accumulated in a
            (1, P) f32 scratch across the grid, ONE launch for projection
            AND loss.  The backward's seed, dlogits_base =
            (softmax(z) − onehot(target)) / B, is emitted in the same
            epilogue (instead of the logits) — the only (P, B, O) array
            that ever touches HBM, and the logits never do.
  backward  ONE kernel reads dlogits_base, scales by the incoming
            per-member cotangent d_per[m] (one (1, 1) block per member
            tile), and emits both dh (dl·W_out, direct per-tile writes)
            and dW_out (dl^T·h, accumulated across batch tiles).
            db_out = d_per ⊙ Σ_b dlogits_base is one XLA fused reduce over
            the array that exists anyway.

Block shapes follow the TPU (8, 128) tiling rule: every per-member operand
is laid out MEMBER-MAJOR with the member axis squeezed out of the block
(bias (P, 1, O) → (1, O) blocks, d_per (P, 1, 1) → (1, 1) blocks,
dlogits_base (P, B, O) → (block_b, O) blocks), so the last two block dims
are always either whole array dims or (8, 128)-aligned.

Grid/tile metadata is the per-block member id (``block_segment_ids``)
scalar-prefetched exactly like kernels/m3_matmul.py: member boundaries
(first/last) are derived from neighbouring ids, so ragged member widths
need no extra metadata.  Padded batch rows carry target −1 and contribute
zero loss and zero dlogits; the output-class axis is padded via −1e30 bias
columns, so softmax assigns them zero mass and their dW rows vanish.

Mixed precision: h/W_out tiles may be bf16; the logits accumulator, the
softmax/lse math, per-member losses, and dlogits_base are always f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.block_diag import tpu_compiler_params


# --------------------------------------------------------------------- #
# forward: projection + softmax-XE epilogue                             #
# --------------------------------------------------------------------- #

def _make_fwd_kernel(inv_b: float, with_dl: bool):
    def kernel(seg_ref, h_ref, w_ref, b_ref, t_ref, *out_and_scratch):
        if with_dl:
            per_ref, dl_ref, acc_ref, per_acc = out_and_scratch
        else:
            per_ref, acc_ref, per_acc = out_and_scratch
        i = pl.program_id(0)
        ni = pl.num_programs(0)
        t = pl.program_id(1)
        nt = pl.num_programs(1)
        seg_t = seg_ref[t]
        first = jnp.logical_or(t == 0, seg_ref[jnp.maximum(t - 1, 0)] != seg_t)
        last = jnp.logical_or(t == nt - 1,
                              seg_ref[jnp.minimum(t + 1, nt - 1)] != seg_t)

        @pl.when(jnp.logical_and(i == 0, t == 0))
        def _zero_per():
            per_acc[...] = jnp.zeros_like(per_acc)

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
            mx = jnp.max(logits, axis=1, keepdims=True)
            ex = jnp.exp(logits - mx)
            den = jnp.sum(ex, axis=1, keepdims=True)
            lse = jnp.log(den) + mx                    # (bb, 1)
            tgt = t_ref[...]                           # (bb, 1) int32
            valid = (tgt >= 0).astype(jnp.float32)     # −1 marks batch pad
            cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            onehot = (cols == tgt).astype(jnp.float32)
            # every reduction keeps its operand 2-D (Mosaic lowers no
            # rank-1 vector reductions)
            nll = (lse - jnp.sum(logits * onehot, axis=1, keepdims=True)
                   ) * valid                           # (bb, 1)
            tot = jnp.sum(nll, axis=0, keepdims=True) * inv_b   # (1, 1)
            p_ = per_acc.shape[1]
            mrow = (jax.lax.broadcasted_iota(jnp.int32, (1, p_), 1)
                    == seg_t).astype(jnp.float32)
            per_acc[...] += mrow * tot
            if with_dl:
                dl_ref[...] = (ex / den - onehot) * (valid * inv_b)

        @pl.when(jnp.logical_and(i == ni - 1, t == nt - 1))
        def _flush_per():
            per_ref[...] = per_acc[...]
    return kernel


def loss_head_fwd(h: jax.Array, w2: jax.Array, b2: jax.Array,
                  targets: jax.Array, seg: jax.Array, num_members: int, *,
                  b_real: int, block_h: int, block_b: int, with_dl: bool,
                  interpret: bool = False):
    """h (B, H), w2 (O, H), b2 (P, O), targets (B, 1) int32 (−1 = pad row)
    → per-member mean NLL (1, P) f32 [, dlogits_base (P, B, O) f32]."""
    b, hh = h.shape
    o = w2.shape[0]
    p = num_members
    grid = (b // block_b, hh // block_h)
    out_shape = [jax.ShapeDtypeStruct((1, p), jnp.float32)]
    out_specs = [pl.BlockSpec((1, p), lambda i, t, seg_r: (0, 0))]
    if with_dl:
        out_shape.append(jax.ShapeDtypeStruct((p, b, o), jnp.float32))
        out_specs.append(pl.BlockSpec((None, block_b, o),
                                      lambda i, t, seg_r: (seg_r[t], i, 0)))
    res = pl.pallas_call(
        _make_fwd_kernel(1.0 / b_real, with_dl),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, block_h),
                             lambda i, t, seg_r: (i, t)),
                pl.BlockSpec((o, block_h), lambda i, t, seg_r: (0, t)),
                pl.BlockSpec((None, 1, o),
                             lambda i, t, seg_r: (seg_r[t], 0, 0)),
                pl.BlockSpec((block_b, 1), lambda i, t, seg_r: (i, 0)),
            ],
            out_specs=out_specs if with_dl else out_specs[0],
            scratch_shapes=[pltpu.VMEM((block_b, o), jnp.float32),
                            pltpu.VMEM((1, p), jnp.float32)],
        ),
        out_shape=out_shape if with_dl else out_shape[0],
        compiler_params=tpu_compiler_params(
            ("arbitrary", "arbitrary"),
            (block_b, block_h), (o, block_h), (1, o), (block_b, 1),
            (1, p), (block_b, o), (block_b, o), (1, p)),
        interpret=interpret,
        name="loss_head_fwd" if with_dl else "loss_head_eval",
    )(seg, h, w2, b2.reshape(p, 1, o), targets)
    return res


# --------------------------------------------------------------------- #
# backward: dh and dW_out in one pass from dlogits_base                 #
# --------------------------------------------------------------------- #

def _bwd_kernel(seg_ref, dper_ref, dl_ref, h_ref, w_ref, dh_ref, dw_ref,
                acc_ref):
    """Grid (t, i): hidden tile OUTER, batch tile INNER.  dh is a direct
    per-(i, t) write; dW_out accumulates over the inner batch tiles in an
    (O, block_h) f32 scratch and flushes on the last one."""
    i = pl.program_id(1)
    nb = pl.num_programs(1)

    dl = dl_ref[...] * dper_ref[...]               # (bb, O) · d_per[member]
    dh_ref[...] = jax.lax.dot_general(
        dl.astype(w_ref.dtype), w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dh_ref.dtype)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        dl.astype(h_ref.dtype), h_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == nb - 1)
    def _flush():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def loss_head_bwd(dper: jax.Array, dl: jax.Array, h: jax.Array,
                  w2: jax.Array, seg: jax.Array, *, block_h: int,
                  block_b: int, interpret: bool = False):
    """dper (P,) f32, dl (P, B, O) f32 → (dh (B, H), dW_out (O, H)) in
    ONE launch."""
    b, hh = h.shape
    o = w2.shape[0]
    p = dl.shape[0]
    grid = (hh // block_h, b // block_b)
    dh, dw = pl.pallas_call(
        _bwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, 1, 1),
                             lambda t, i, seg_r: (seg_r[t], 0, 0)),
                pl.BlockSpec((None, block_b, o),
                             lambda t, i, seg_r: (seg_r[t], i, 0)),
                pl.BlockSpec((block_b, block_h),
                             lambda t, i, seg_r: (i, t)),
                pl.BlockSpec((o, block_h), lambda t, i, seg_r: (0, t)),
            ],
            out_specs=[
                pl.BlockSpec((block_b, block_h),
                             lambda t, i, seg_r: (i, t)),
                pl.BlockSpec((o, block_h), lambda t, i, seg_r: (0, t)),
            ],
            scratch_shapes=[pltpu.VMEM((o, block_h), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hh), h.dtype),
            jax.ShapeDtypeStruct((o, hh), w2.dtype),
        ],
        compiler_params=tpu_compiler_params(
            ("arbitrary", "arbitrary"),
            (1, 1), (block_b, o), (block_b, block_h), (o, block_h),
            (block_b, block_h), (o, block_h), (o, block_h)),
        interpret=interpret,
        name="loss_head_bwd",
    )(seg, dper.reshape(p, 1, 1), dl, h, w2)
    return dh, dw
