"""Tiling and VMEM budgets shared by the population kernels (fused input
layer, fused mid layer, loss head, inference head): how many hidden blocks
one grid step takes, and the Mosaic compiler parameters a kernel's live
blocks ask for.
"""
from __future__ import annotations

import math

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
