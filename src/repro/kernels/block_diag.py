"""Block-diagonal GEMM — the mid-layer projection of layered populations
(DESIGN.md §3).

Member m's units in layer l+1 contract ONLY member m's units in layer l, so
the fused l→l+1 weight is block-diagonal with one (O_m × I_m) block per
member.  Instead of a Python loop of per-bucket einsums this runs as ONE
dense segment-blocked matmul: the weight is stored as a flat array of
(block × block) tiles (member-major, row-major over each member's tile grid,
plus one shared identity tile for pass-through members).

Members have DIFFERENT fan-ins, so the reduction is RAGGED.  The grid is
therefore flattened to one step per REAL (output tile, reduction k) pair —
``BlockDiagLayout.s_in/s_w/s_out`` select, for grid step s,

    input tile   s_in[s]
    weight tile  s_w[s]       (the moe_gemm weight-block-selection trick)
    output tile  s_out[s]     (revisits are consecutive grid steps)

with ``s_first/s_last`` flagging the accumulator init/flush edges.  This
replaces the earlier dense (out_tiles × k_max) grid whose clamped re-reads
burned a dead step for every tile below the maximum fan-in — the
BENCH_deep hbm_gap regression.  f32 VMEM accumulation, no scatter.

The backward pass reuses the SAME forward kernel for dh (block-diagonal with
each member block transposed — a static tile permutation + per-tile
transpose, metadata ``s_*_t``), and ``block_diag_dw`` accumulates each
parameter tile's dy^T·x over batch tiles (grid (param_tiles, b_tiles)).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# On-chip vector memory (VMEM) of one TPU v5e TensorCore, and Mosaic's
# default scoped limit there: the budget below never asks for less than
# the default nor for more than the chip has.
VMEM_BYTES = 128 * 1024 * 1024
_VMEM_DEFAULT_LIMIT = 16 * 1024 * 1024
# bytes of its largest operand one grid step of a hidden-tiled kernel
# (loss head, fused input layer) aims to move: enough to hide the fixed
# cost of a step behind the HBM transfer, little enough to double-buffer
TILE_BYTES = 2 * 1024 * 1024


def tile_blocks(n_blocks: int, block_bytes: int, *, cap: int | None = None,
                multiple: int = 1) -> int:
    """Hidden blocks a grid step takes when each block brings
    ``block_bytes`` of its largest operand: about ``TILE_BYTES``, at most
    ``cap``, rounded down to ``multiple`` (and at least that) unless one
    step takes every block."""
    g = max(1, TILE_BYTES // block_bytes)
    if cap is not None:
        g = min(g, cap)
    if g >= n_blocks:
        return n_blocks
    return max(multiple, g // multiple * multiple)


def tpu_compiler_params(dimension_semantics, *block_shapes, dtype_bytes=4):
    """Mosaic compiler params: dimension semantics (reduction dims are
    'arbitrary', independent dims 'parallel') and a VMEM budget derived from
    the kernel's live blocks — 4× their bytes (double-buffered pipeline +
    accumulator and epilogue temporaries), at least Mosaic's default limit,
    at most the chip's VMEM.  A kernel whose double-buffered blocks alone
    exceed the chip fails here, naming its size, instead of inside the
    compiler."""
    need = sum(math.prod(s) * dtype_bytes for s in block_shapes)
    if 2 * need > VMEM_BYTES:
        raise ValueError(
            f"kernel blocks need {2 * need} B of VMEM double-buffered, the "
            f"chip has {VMEM_BYTES} B: shrink the batch or block tiles")
    budget = min(max(4 * need, _VMEM_DEFAULT_LIMIT), VMEM_BYTES)
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=int(budget))


# --------------------------------------------------------------------- #
# forward (also computes dh when fed transposed metadata)               #
# --------------------------------------------------------------------- #

def _fwd_kernel(ins_ref, w_ref_ids, outs_ref, first_ref, last_ref,
                x_ref, wb_ref, y_ref, acc_ref):
    s = pl.program_id(1)

    @pl.when(first_ref[s] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (block_b, blk) @ (blk, blk)^T on the MXU, f32 accumulate; weight
    # tiles are (out_rows, in_cols) so the contraction is over dim 1/1.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], wb_ref[...][0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last_ref[s] == 1)
    def _flush():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def block_diag_fwd(x: jax.Array, wb: jax.Array, s_in: jax.Array,
                   s_w: jax.Array, s_out: jax.Array, s_first: jax.Array,
                   s_last: jax.Array, *, n_out_tiles: int, n_steps: int,
                   block: int, block_b: int,
                   interpret: bool = False) -> jax.Array:
    """x (B, in_tiles·blk), wb (n_tiles, blk, blk) → y (B, out_tiles·blk)."""
    b = x.shape[0]
    grid = (b // block_b, n_steps)
    return pl.pallas_call(
        _fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, block),
                             lambda i, s, ins, w, outs, fr, la: (i, ins[s])),
                pl.BlockSpec((1, block, block),
                             lambda i, s, ins, w, outs, fr, la: (w[s], 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (block_b, block),
                lambda i, s, ins, w, outs, fr, la: (i, outs[s])),
            scratch_shapes=[pltpu.VMEM((block_b, block), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_out_tiles * block), x.dtype),
        compiler_params=tpu_compiler_params(
            ("parallel", "arbitrary"),
            (block_b, block), (block, block), (block_b, block),
            (block_b, block)),
        interpret=interpret,
        name="block_diag_fwd",
    )(s_in, s_w, s_out, s_first, s_last, x, wb)


# --------------------------------------------------------------------- #
# backward: dW tiles                                                    #
# --------------------------------------------------------------------- #

def _dw_kernel(ot_ref, it_ref, dy_ref, x_ref, dw_ref, acc_ref):
    i = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dW[o, i] = sum_b dy[b, o] · x[b, i]  — contract the batch tile
    acc_ref[...] += jax.lax.dot_general(
        dy_ref[...], x_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == nb - 1)
    def _flush():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)[None]


def block_diag_dw(dy: jax.Array, x: jax.Array, wb_out_tile: jax.Array,
                  wb_in_tile: jax.Array, *, n_param_blocks: int, block: int,
                  block_b: int, interpret: bool = False) -> jax.Array:
    """dy (B, out_tiles·blk), x (B, in_tiles·blk) → dWB (n_param, blk, blk).

    Parameter tile q reads dy tile wb_out_tile[q] against x tile
    wb_in_tile[q]; batch is the (inner) reduction grid dimension."""
    b = x.shape[0]
    grid = (n_param_blocks, b // block_b)
    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, block),
                             lambda q, i, ot, it: (i, ot[q])),
                pl.BlockSpec((block_b, block),
                             lambda q, i, ot, it: (i, it[q])),
            ],
            out_specs=pl.BlockSpec((1, block, block),
                                   lambda q, i, ot, it: (q, 0, 0)),
            scratch_shapes=[pltpu.VMEM((block, block), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_param_blocks, block, block),
                                       dy.dtype),
        compiler_params=tpu_compiler_params(
            ("parallel", "arbitrary"),
            (block_b, block), (block_b, block), (block, block),
            (block, block)),
        interpret=interpret,
        name="block_diag_dw",
    )(wb_out_tile, wb_in_tile, dy, x)
