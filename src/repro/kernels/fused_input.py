"""Fused input-layer kernel: dense input GEMM + per-member bias +
per-segment activation in ONE Pallas pass (DESIGN.md §9).

The mid layers got their §7 epilogue in kernels/fused_layer.py, but the
INPUT projection — the one dense (non-block-diagonal) GEMM of the stack,
shared x (B, F) against the stacked first-layer weight (H, F) — still ran
as an XLA dot followed by a standalone seg_act pass: z0 round-trips
through HBM twice.  This kernel folds the same epilogue into the input
GEMM:

  forward   y  = act(x·W_in^T + b_in) · mask   (one kernel, z0 never in HBM)
            g' = act'(x·W_in^T + b_in) · mask  (emitted instead of z0 when a
                                               VJP will consume it)
  backward  du = dy ⊙ g' formed in-register in ONE kernel that emits both
            dx (du·W_in, accumulated across hidden tiles in a full-batch
            f32 scratch) and dW_in (du^T·x, accumulated across the inner
            batch tiles in one (block, block_f) f32 scratch — on-chip
            memory independent of H).  db = Σ_b dy·g' is one XLA fused
            reduce over arrays that exist anyway.

Grid layout: the hidden axis is tiled at the population block size (the
per-block activation id is scalar-prefetched, dispatched via lax.switch on
the flush step, exactly like the mid layers); the feature axis F is tiled
at ``block_f`` (the whole padded F when F ≤ 128, else 128 lanes) as the
reduction dimension.

Mixed precision: operand tiles may be bf16; accumulators and the bias add
are always f32, outputs are cast back to the operand dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.block_diag import tpu_compiler_params
from repro.kernels.epilogue import VAL_BRANCHES, VAL_DERIV_BRANCHES


def pick_block_f(f_pad: int) -> int:
    """Feature-axis tile: whole (padded) F when it fits a lane register,
    else 128-lane tiles."""
    return f_pad if f_pad <= 128 else 128


# --------------------------------------------------------------------- #
# forward: dense GEMM + bias + activation epilogue                      #
# --------------------------------------------------------------------- #

def _make_fwd_kernel(with_deriv: bool):
    def kernel(act_ref, x_ref, w_ref, b_ref, m_ref, *out_and_scratch):
        if with_deriv:
            y_ref, g_ref, acc_ref = out_and_scratch
        else:
            y_ref, acc_ref = out_and_scratch
        t = pl.program_id(1)
        kf = pl.program_id(2)
        nf = pl.num_programs(2)

        @pl.when(kf == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(kf == nf - 1)
        def _epilogue():
            u = acc_ref[...] + b_ref[...].astype(jnp.float32)
            m = m_ref[...].astype(jnp.float32)
            if with_deriv:
                y, g = jax.lax.switch(act_ref[t], VAL_DERIV_BRANCHES, u)
                y_ref[...] = (y * m).astype(y_ref.dtype)
                g_ref[...] = (g * m).astype(g_ref.dtype)
            else:
                y = jax.lax.switch(act_ref[t], VAL_BRANCHES, u)
                y_ref[...] = (y * m).astype(y_ref.dtype)
    return kernel


def fused_input_fwd(x: jax.Array, w: jax.Array, bias: jax.Array,
                    mask: jax.Array, act_ids: jax.Array, *, block: int,
                    block_b: int, with_deriv: bool,
                    interpret: bool = False):
    """x (B, F_pad), w (H, F_pad), bias/mask (1, H), per-block act ids
    (H/block,) → y (B, H) [, g' (B, H) when ``with_deriv``]."""
    b, f_pad = x.shape
    h = w.shape[0]
    block_f = pick_block_f(f_pad)
    grid = (b // block_b, h // block, f_pad // block_f)
    out_shape = [jax.ShapeDtypeStruct((b, h), x.dtype)]
    out_specs = [pl.BlockSpec((block_b, block),
                              lambda i, t, kf, act: (i, t))]
    if with_deriv:
        out_shape.append(jax.ShapeDtypeStruct((b, h), x.dtype))
        out_specs.append(pl.BlockSpec((block_b, block),
                                      lambda i, t, kf, act: (i, t)))
    y = pl.pallas_call(
        _make_fwd_kernel(with_deriv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, block_f),
                             lambda i, t, kf, act: (i, kf)),
                pl.BlockSpec((block, block_f),
                             lambda i, t, kf, act: (t, kf)),
                pl.BlockSpec((1, block), lambda i, t, kf, act: (0, t)),
                pl.BlockSpec((1, block), lambda i, t, kf, act: (0, t)),
            ],
            out_specs=out_specs if with_deriv else out_specs[0],
            scratch_shapes=[pltpu.VMEM((block_b, block), jnp.float32)],
        ),
        out_shape=out_shape if with_deriv else out_shape[0],
        compiler_params=tpu_compiler_params(
            ("parallel", "arbitrary", "arbitrary"),
            (block_b, block_f), (block, block_f), (1, block), (1, block),
            (block_b, block), (block_b, block), (block_b, block)),
        interpret=interpret,
        name="fused_input_fwd" if with_deriv else "fused_input_infer",
    )(act_ids, x, w, bias, mask)
    return y


# --------------------------------------------------------------------- #
# forward, int8 weights: in-loop dequant + dense GEMM + epilogue        #
# --------------------------------------------------------------------- #

def _int8_fwd_kernel(act_ref, sc_ref, x_ref, w_ref, b_ref, m_ref, y_ref,
                     acc_ref):
    """Int8-weight twin of ``_make_fwd_kernel(False)`` (DESIGN.md §12):
    one f32 scale per hidden row block (each owned by one member), shared
    across the feature reduction tiles — the scales ride the scalar
    prefetch stream (indexed ``sc_ref[t]``, no per-step blocked operand),
    and the int8 weight tile is dequantized on the VPU right before the
    contraction.  Same grid, same epilogue, forward-only by
    construction."""
    t = pl.program_id(1)
    kf = pl.program_id(2)
    nf = pl.num_programs(2)

    @pl.when(kf == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.float32) * sc_ref[t]
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kf == nf - 1)
    def _epilogue():
        u = acc_ref[...] + b_ref[...].astype(jnp.float32)
        m = m_ref[...].astype(jnp.float32)
        y = jax.lax.switch(act_ref[t], VAL_BRANCHES, u)
        y_ref[...] = (y * m).astype(y_ref.dtype)


def fused_input_int8_fwd(x: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                         bias: jax.Array, mask: jax.Array,
                         act_ids: jax.Array, *, block: int, block_b: int,
                         interpret: bool = False):
    """x (B, F_pad), w_q (H, F_pad) int8, w_scale (H/block,) f32
    scalar-prefetch, bias/mask (1, H), per-block act ids (H/block,) →
    y (B, H)."""
    b, f_pad = x.shape
    h = w_q.shape[0]
    block_f = pick_block_f(f_pad)
    grid = (b // block_b, h // block, f_pad // block_f)
    return pl.pallas_call(
        _int8_fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, block_f),
                             lambda i, t, kf, act, sc: (i, kf)),
                pl.BlockSpec((block, block_f),
                             lambda i, t, kf, act, sc: (t, kf)),
                pl.BlockSpec((1, block), lambda i, t, kf, act, sc: (0, t)),
                pl.BlockSpec((1, block), lambda i, t, kf, act, sc: (0, t)),
            ],
            out_specs=pl.BlockSpec((block_b, block),
                                   lambda i, t, kf, act, sc: (i, t)),
            scratch_shapes=[pltpu.VMEM((block_b, block), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h), x.dtype),
        compiler_params=tpu_compiler_params(
            ("parallel", "arbitrary", "arbitrary"),
            (block_b, block_f), (block, block_f), (1, block),
            (1, block), (block_b, block), (block_b, block)),
        interpret=interpret,
        name="fused_input_infer_int8",
    )(act_ids, w_scale, x, w_q, bias, mask)


# --------------------------------------------------------------------- #
# backward: dx and dw in one pass, du = dy·g' in-register               #
# --------------------------------------------------------------------- #

def _bwd_kernel(dy_ref, g_ref, x_ref, w_ref, dx_ref, dw_ref,
                dx_acc_ref, dw_acc_ref):
    """Grid (kf, t, i): feature tile OUTER (each emits an independent dx /
    dw column stripe), hidden tile middle, batch tile INNER — the same
    two-level shape as the mid-layer backward (kernels/fused_layer.py).

    dw: the (t, kf) parameter tile accumulates over the inner batch tiles
    in a (block, block_f) f32 scratch and flushes on the last one, so the
    scratch does not grow with the fused hidden width H.
    dx: each batch tile's running sum over hidden tiles lives in its rows
    of a full-batch (B, block_f) f32 scratch; x and dx are whole-batch
    blocks resident for the feature stripe (their block index depends on
    kf only), so x is read once per stripe and dx is written back once."""
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    i = pl.program_id(2)
    nb = pl.num_programs(2)
    bb = dy_ref.shape[0]
    rows = pl.ds(pl.multiple_of(i * bb, bb), bb)

    du = dy_ref[...] * g_ref[...]          # dz0 never exists outside
                                           # this register
    prev = dx_acc_ref[rows, :]
    prev = jnp.where(t == 0, jnp.zeros_like(prev), prev)
    acc = prev + jax.lax.dot_general(
        du, w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dx_acc_ref[rows, :] = acc

    @pl.when(t == nt - 1)
    def _flush_dx():
        dx_ref[rows, :] = acc.astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _init_dw():
        dw_acc_ref[...] = jnp.zeros_like(dw_acc_ref)

    dw_acc_ref[...] += jax.lax.dot_general(
        du, x_ref[rows, :],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == nb - 1)
    def _flush_dw():
        dw_ref[...] = dw_acc_ref[...].astype(dw_ref.dtype)


def fused_input_bwd(dy: jax.Array, gp: jax.Array, x: jax.Array,
                    w: jax.Array, *, block: int, block_b: int,
                    interpret: bool = False):
    """dy, g' (B, H), x (B, F_pad), w (H, F_pad) → (dx (B, F_pad),
    dW (H, F_pad)) in ONE launch.  Batch must be padded to a block_b
    multiple (the wrapper's ``_pad_axis`` guarantees it)."""
    b, h = dy.shape
    f_pad = x.shape[1]
    if b % block_b:
        raise ValueError(
            f"fused input backward needs batch padded to a block_b "
            f"multiple, got batch {b} with block_b {block_b}")
    block_f = pick_block_f(f_pad)
    grid = (f_pad // block_f, h // block, b // block_b)
    dx, dw = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block), lambda kf, t, i: (i, t)),
            pl.BlockSpec((block_b, block), lambda kf, t, i: (i, t)),
            pl.BlockSpec((b, block_f), lambda kf, t, i: (0, kf)),
            pl.BlockSpec((block, block_f), lambda kf, t, i: (t, kf)),
        ],
        out_specs=[
            pl.BlockSpec((b, block_f), lambda kf, t, i: (0, kf)),
            pl.BlockSpec((block, block_f), lambda kf, t, i: (t, kf)),
        ],
        scratch_shapes=[pltpu.VMEM((b, block_f), jnp.float32),
                        pltpu.VMEM((block, block_f), jnp.float32)],
        out_shape=[
            jax.ShapeDtypeStruct((b, f_pad), dy.dtype),
            jax.ShapeDtypeStruct((h, f_pad), dy.dtype),
        ],
        compiler_params=tpu_compiler_params(
            ("parallel", "arbitrary", "arbitrary"),
            (block_b, block), (block_b, block), (b, block_f),
            (block, block_f), (b, block_f), (block, block_f),
            (b, block_f), (block, block_f)),
        interpret=interpret,
        name="fused_input_bwd",
    )(dy, gp, x, w)
    return dx, dw
