"""Segment-blocked matmul — the TPU-native M3 (DESIGN.md §2).

Forward:   y[b, m, o] = sum_{j in segment m} h[b, j] * w2[o, j]
with every member's hidden slice padded to a multiple of ``block_h`` so each
hidden tile belongs to exactly one member.  The paper's scatter-add becomes
*output-block selection*: grid step (i, t) computes a dense
(block_b × block_h)·(block_h × O) MXU matmul and accumulates it (f32 VMEM
scratch) into output block (i, seg[t]); ``seg`` arrives via scalar prefetch
so the index map is known before the tile is fetched.  Because members are
contiguous, revisits of an output block are consecutive grid steps — the
standard Pallas reduction pattern (no atomics, no (B,O,H) intermediate).

Backward (two more kernels, same trick transposed):
    dh[b, j] = dot(dy[b, seg(j), :], w2[:, j])        — gather-matmul per tile
    dw2[o, j] = sum_b h[b, j] * dy[b, seg(j), o]      — accumulate over b tiles
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# --------------------------------------------------------------------- #
# forward                                                               #
# --------------------------------------------------------------------- #

def _fwd_kernel(seg_ref, h_ref, w_ref, y_ref, acc_ref):
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    seg_t = seg_ref[t]
    first = jnp.logical_or(t == 0, seg_ref[jnp.maximum(t - 1, 0)] != seg_t)
    last = jnp.logical_or(t == nt - 1, seg_ref[jnp.minimum(t + 1, nt - 1)] != seg_t)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (block_b, block_h) @ (block_h, O) on the MXU, f32 accumulate
    acc_ref[...] += jax.lax.dot_general(
        h_ref[...], w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last)
    def _flush():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)[:, None, :]


def m3_matmul_fwd(h: jax.Array, w2: jax.Array, block_seg_ids: jax.Array,
                  num_members: int, *, block_h: int, block_b: int,
                  interpret: bool = False) -> jax.Array:
    b, hh = h.shape
    o = w2.shape[0]
    nt = hh // block_h
    nb = b // block_b
    grid = (nb, nt)
    return pl.pallas_call(
        _fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, block_h), lambda i, t, seg: (i, t)),
                pl.BlockSpec((o, block_h), lambda i, t, seg: (0, t)),
            ],
            out_specs=pl.BlockSpec((block_b, 1, o),
                                   lambda i, t, seg: (i, seg[t], 0)),
            scratch_shapes=[pltpu.VMEM((block_b, o), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, num_members, o), h.dtype),
        interpret=interpret,
        name="m3_fwd",
    )(block_seg_ids, h, w2)


# --------------------------------------------------------------------- #
# backward: dh                                                          #
# --------------------------------------------------------------------- #

def _dh_kernel(seg_ref, dy_ref, w_ref, dh_ref):
    # dy block (block_b, 1, O) is the member's output grad; one shot per tile.
    dy = dy_ref[...][:, 0, :]                       # (block_b, O)
    dh_ref[...] = jax.lax.dot_general(
        dy, w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dh_ref.dtype)


def m3_matmul_dh(dy: jax.Array, w2: jax.Array, block_seg_ids: jax.Array,
                 *, block_h: int, block_b: int,
                 interpret: bool = False) -> jax.Array:
    b, _, o = dy.shape
    hh = w2.shape[1]
    grid = (b // block_b, hh // block_h)
    return pl.pallas_call(
        _dh_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, 1, o), lambda i, t, seg: (i, seg[t], 0)),
                pl.BlockSpec((o, block_h), lambda i, t, seg: (0, t)),
            ],
            out_specs=pl.BlockSpec((block_b, block_h), lambda i, t, seg: (i, t)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, hh), dy.dtype),
        interpret=interpret,
        name="m3_dh",
    )(block_seg_ids, dy, w2)


# --------------------------------------------------------------------- #
# backward: dw2                                                         #
# --------------------------------------------------------------------- #

def _dw_kernel(seg_ref, dy_ref, h_ref, dw_ref, acc_ref):
    i = pl.program_id(1)                            # batch tile (inner dim)
    nb = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dy = dy_ref[...][:, 0, :]                       # (block_b, O)
    # (O, block_b) @ (block_b, block_h) -> (O, block_h)
    acc_ref[...] += jax.lax.dot_general(
        dy, h_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == nb - 1)
    def _flush():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def m3_matmul_dw(dy: jax.Array, h: jax.Array, block_seg_ids: jax.Array,
                 *, block_h: int, block_b: int,
                 interpret: bool = False) -> jax.Array:
    b, _, o = dy.shape
    hh = h.shape[1]
    grid = (hh // block_h, b // block_b)            # batch is the reduction dim
    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, 1, o), lambda t, i, seg: (i, seg[t], 0)),
                pl.BlockSpec((block_b, block_h), lambda t, i, seg: (i, t)),
            ],
            out_specs=pl.BlockSpec((o, block_h), lambda t, i, seg: (0, t)),
            scratch_shapes=[pltpu.VMEM((o, block_h), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((o, hh), h.dtype),
        interpret=interpret,
        name="m3_dw",
    )(block_seg_ids, dy, h)
