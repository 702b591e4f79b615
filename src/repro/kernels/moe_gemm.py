"""Grouped GEMM — the M3 segment trick applied along the *row* axis.

MoE expert computation: tokens sorted by expert id form contiguous row
segments; each segment multiplies its own expert weight.  Where M3 segments
the reduction (hidden) axis by member, this segments the rows: the
scalar-prefetched per-tile expert id selects the *weight* block.

    y[t] = x[t] @ w[expert(t)]        x (T, D), w (E, D, F) -> y (T, F)

Grid (t_tiles, f_tiles, d_tiles); accumulation over d in f32 VMEM scratch.
The wrapper (ops.moe_gemm) requires every expert's token run padded to a
multiple of ``block_t`` — the MoE layer guarantees this by capacity padding,
exactly how the population layout guarantees 128-aligned member slices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(eid_ref, x_ref, w_ref, y_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (block_t, block_d) @ (block_d, block_f)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...][0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def moe_gemm(x: jax.Array, w: jax.Array, block_expert_ids: jax.Array, *,
             block_t: int, block_d: int, block_f: int,
             interpret: bool = False) -> jax.Array:
    t, d = x.shape
    e, _, f = w.shape
    grid = (t // block_t, f // block_f, d // block_d)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_t, block_d), lambda i, j, k, eid: (i, k)),
                pl.BlockSpec((1, block_d, block_f),
                             lambda i, j, k, eid: (eid[i], k, j)),
            ],
            out_specs=pl.BlockSpec((block_t, block_f),
                                   lambda i, j, k, eid: (i, j)),
            scratch_shapes=[pltpu.VMEM((block_t, block_f), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t, f), x.dtype),
        interpret=interpret,
        name="moe_gemm",
    )(block_expert_ids, x, w)
