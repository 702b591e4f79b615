"""Segmented activation kernel: apply a *different* activation function per
hidden block in a single pass over the tensor.

The paper applies per-member activations by split→activate→concat (or by
masking, which reads the tensor 10×).  TPU-native version: the per-block
activation id is scalar-prefetched; each tile is read once from VMEM and
dispatched through ``lax.switch`` over the kernel forms of the ten paper
activations (kernels/epilogue.py); the padding mask is fused into the same
pass (zero HBM overhead).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogue import DERIV_BRANCHES, VAL_BRANCHES


def _kernel(act_ref, h_ref, mask_ref, out_ref):
    t = pl.program_id(1)
    x = h_ref[...]
    y = jax.lax.switch(act_ref[t], VAL_BRANCHES, x)
    out_ref[...] = y * mask_ref[...].astype(y.dtype)


def seg_act(h: jax.Array, block_act_ids: jax.Array, mask: jax.Array, *,
            block_h: int, block_b: int, interpret: bool = False) -> jax.Array:
    """h (B, H), block_act_ids (H//block_h,), mask (1, H) -> (B, H)."""
    b, hh = h.shape
    grid = (b // block_b, hh // block_h)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, block_h), lambda i, t, act: (i, t)),
                pl.BlockSpec((1, block_h), lambda i, t, act: (0, t)),
            ],
            out_specs=pl.BlockSpec((block_b, block_h), lambda i, t, act: (i, t)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, hh), h.dtype),
        interpret=interpret,
        name="seg_act_fwd",
    )(block_act_ids, h, mask)


def _bwd_kernel(act_ref, h_ref, dy_ref, mask_ref, out_ref):
    t = pl.program_id(1)
    x = h_ref[...]
    g = dy_ref[...] * mask_ref[...].astype(dy_ref.dtype)
    out_ref[...] = g * jax.lax.switch(act_ref[t], DERIV_BRANCHES, x)


def seg_act_bwd(h: jax.Array, dy: jax.Array, block_act_ids: jax.Array,
                mask: jax.Array, *, block_h: int, block_b: int,
                interpret: bool = False) -> jax.Array:
    """dL/dh of ``seg_act``: dy·mask times each block's activation
    derivative in the same one-pass tile-wise ``lax.switch`` dispatch as the
    forward (the cotangent of the fused mask-multiply is just another
    elementwise factor, so it fuses into the same tile read)."""
    b, hh = h.shape
    grid = (b // block_b, hh // block_h)
    return pl.pallas_call(
        _bwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, block_h), lambda i, t, act: (i, t)),
                pl.BlockSpec((block_b, block_h), lambda i, t, act: (i, t)),
                pl.BlockSpec((1, block_h), lambda i, t, act: (0, t)),
            ],
            out_specs=pl.BlockSpec((block_b, block_h), lambda i, t, act: (i, t)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, hh), h.dtype),
        interpret=interpret,
        name="seg_act_bwd",
    )(block_act_ids, h, dy, mask)
