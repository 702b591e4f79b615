"""Fused input-layer kernel: dense input GEMM + per-member bias +
per-segment activation in ONE Pallas pass per direction (DESIGN.md §9).

Layer 0 is the one dense (non-block-diagonal) GEMM of the stack: the
shared batch x (B, F) against the stacked first-layer weight (H, F).  The
§7 epilogue runs while each block's pre-activation is still in VMEM:

  forward   y  = act(x·W_in^T + b_in) · mask   (z0 never in HBM)
            g' = act'(x·W_in^T + b_in) · mask  (emitted too when a VJP will
                                               consume it)
  backward  du = dy ⊙ g' formed once per hidden block, in-register, and
            from it, in the same read of dy and g':
              dW_in = du^T·x   the block's rows, the whole batch in 128-row
                               contractions, each added to the sum of the
                               ones before it;
              db_in = Σ_b du   a (1, block) row of the bias gradient, in
                               64-row groups (8-row vregs in turn, then the
                               sublanes by halves), the groups in turn;
              dx    = Σ du·W_in, accumulated over the hidden tiles in a
                      (B, F_pad) f32 scratch — only when x is perturbed
                      (``custom_vjp`` symbolic zeros, ``ops._fin_fwd``):
                      a training step differentiates the parameters, and
                      layer 0's input is the batch.

Rounding.  The forward passes z0 through an f32 scratch and adds the bias
to what it reads back: added to a contraction's result directly, the bias
is folded by the TPU compiler into the contraction's accumulator, which
rounds z0 otherwise.  With that and the two batch-sum orders above, the
layer rounds as the batch-tiled kernel and the XLA column reduce (at the
paper grid's 1,280,000 lanes) that made these numbers before did, so a
member whose pre-activation sits at a discontinuous activation's
threshold (hardshrink's ±0.5) falls on the same side whatever the tiling.

Tiling.  Each grid step takes the WHOLE (padded) batch and ``G``
consecutive hidden blocks — about ``tiling.TILE_BYTES`` (2 MiB) of
the largest operand a block brings, B_pad rows of the (B, H) arrays or
``block_f`` columns of w_in and dW_in; 16 blocks of 128 lanes at B = 256
(``tile_plan``) — so the fixed cost of a grid step is spread over enough
bytes to approach the HBM bound: 625 steps a pass at 10,000 one-block
members, not the 20,000 of one (128, 128) tile a step.  The grid is
(hidden tile, feature tile), the feature tile innermost (the whole padded
F when F ≤ 128, else 128-lane reduction tiles), so x stays resident.
Inside a step a ``fori_loop`` walks the tile's blocks; each block keeps
its own activation through ``lax.switch`` on the scalar-prefetched id
(members are sorted by activation, so consecutive blocks mostly take the
same branch), and the loop's trip count stops at the last real block, so
a partly filled last tile needs no padding of H.  The forward keeps a
batch-tile axis for the serving path (``ops.fused_input_infer``, at most
``INFER_BLOCK_B`` rows a step); training takes the whole batch, as the
loss head does, which holds one block's rows of each (B, H) array in
VMEM: tens of thousands of rows at most.

Mixed precision: operand tiles may be bf16; accumulators, the bias add,
du and db are always f32, outputs are cast back to the operand dtype.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import tile_blocks, tpu_compiler_params
from repro.kernels.epilogue import VAL_BRANCHES, VAL_DERIV_BRANCHES


def padded_features(f: int) -> int:
    """F padded to the kernels' feature tiling: a multiple of 8 (one lane
    register) when F ≤ 128, else of the 128-lane reduction tile."""
    mult = 8 if f <= 128 else 128
    return f + (-f) % mult


def pick_block_f(f_pad: int) -> int:
    """Feature-axis tile: whole (padded) F when it fits a lane register,
    else 128-lane tiles."""
    return f_pad if f_pad <= 128 else 128


class TilePlan(NamedTuple):
    g: int          # hidden blocks per grid step
    steps: int      # grid steps per pass (hidden tiles × feature tiles)
    makes_dx: bool  # whether the backward computes and stores dx


def tile_plan(b: int, h: int, f: int, block: int, *, itemsize: int = 4,
              x_perturbed: bool = False) -> TilePlan:
    """How both kernels tile a (B, F) batch against an (H, F) weight: the
    batch padded to 8 rows, ``G`` blocks of ``block`` lanes a step, and dx
    only for a perturbed x.  A block brings B_pad rows of each (B, H)
    operand and ``block_f`` columns of each (H, F_pad) one (w_in, dW_in);
    ``G`` holds the larger of the two at about ``tiling.TILE_BYTES``.
    At paper-40k's per-chip shape, ``tile_plan(256, 1_280_000, 100, 128)``
    is 16 blocks and 625 steps a pass, with no dx."""
    nb = h // block
    f_pad = padded_features(f)
    block_f = pick_block_f(f_pad)
    b_pad = -(-b // 8) * 8
    g = tile_blocks(nb, max(b_pad, block_f) * block * itemsize)
    return TilePlan(g, -(-nb // g) * (f_pad // block_f), x_perturbed)


def _block_cols(j, block: int):
    return pl.ds(pl.multiple_of(j * block, block), block)


# --------------------------------------------------------------------- #
# forward: dense GEMM + bias + activation epilogue                      #
# --------------------------------------------------------------------- #

def _make_fwd_kernel(with_deriv: bool, g: int, block: int, nb: int,
                     nf: int):
    def kernel(act_ref, x_ref, w_ref, b_ref, m_ref, *out_and_scratch):
        y_ref = out_and_scratch[0]
        g_ref = out_and_scratch[1] if with_deriv else None
        acc_ref = out_and_scratch[-1]
        t = pl.program_id(1)
        kf = pl.program_id(2)

        def body(j, carry):
            cols = _block_cols(j, block)
            # the block's sum over the feature tiles lives in its columns of
            # the tile's f32 scratch; the bias is added to what is read back
            # on the last feature tile, so the compiler cannot fold it into
            # the contraction's accumulator (that rounds z0 otherwise)
            acc_ref[:, cols] = (
                jnp.where(kf == 0, 0.0, acc_ref[:, cols])
                + jax.lax.dot_general(
                    x_ref[...], w_ref[cols, :],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32))

            @pl.when(kf == nf - 1)
            def _epilogue():
                u = acc_ref[:, cols] + b_ref[:, cols].astype(jnp.float32)
                m = m_ref[:, cols].astype(jnp.float32)
                a = act_ref[t * g + j]
                if with_deriv:
                    y, d = jax.lax.switch(a, VAL_DERIV_BRANCHES, u)
                    g_ref[:, cols] = (d * m).astype(g_ref.dtype)
                else:
                    y = jax.lax.switch(a, VAL_BRANCHES, u)
                y_ref[:, cols] = (y * m).astype(y_ref.dtype)
            return carry

        jax.lax.fori_loop(0, jnp.minimum(g, nb - t * g), body, 0)
    return kernel


def fused_input_fwd(x: jax.Array, w: jax.Array, bias: jax.Array,
                    mask: jax.Array, act_ids: jax.Array, *, block: int,
                    block_b: int, with_deriv: bool,
                    interpret: bool = False):
    """x (B, F_pad), w (H, F_pad), bias/mask (1, H), per-block act ids
    (H/block,) → y (B, H) [, g' (B, H) when ``with_deriv``].  Each grid
    step takes ``block_b`` rows (training: the whole batch) and
    ``tile_plan``'s hidden blocks."""
    b, f_pad = x.shape
    h = w.shape[0]
    nb = h // block
    block_f = pick_block_f(f_pad)
    nf = f_pad // block_f
    g = tile_plan(block_b, h, f_pad, block, itemsize=x.dtype.itemsize).g
    width = g * block
    grid = (b // block_b, -(-nb // g), nf)
    n_out = 2 if with_deriv else 1
    out_shape = [jax.ShapeDtypeStruct((b, h), x.dtype)] * n_out
    out_specs = [pl.BlockSpec((block_b, width),
                              lambda i, t, kf, act: (i, t))] * n_out
    scratch = [pltpu.VMEM((block_b, width), jnp.float32)]
    out = pl.pallas_call(
        _make_fwd_kernel(with_deriv, g, block, nb, nf),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, block_f),
                             lambda i, t, kf, act: (i, kf)),
                pl.BlockSpec((width, block_f),
                             lambda i, t, kf, act: (t, kf)),
                pl.BlockSpec((1, width), lambda i, t, kf, act: (0, t)),
                pl.BlockSpec((1, width), lambda i, t, kf, act: (0, t)),
            ],
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=tpu_compiler_params(
            ("parallel", "arbitrary", "arbitrary"),
            (block_b, block_f), (width, block_f), (1, width), (1, width),
            *[(block_b, width)] * (n_out + len(scratch))),
        interpret=interpret,
        name="fused_input_fwd" if with_deriv else "fused_input_infer",
    )(act_ids, x, w, bias, mask)
    return tuple(out) if with_deriv else out[0]


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
    contraction.  Same epilogue, forward-only by construction; its grid
    still takes one (block_b, block) tile a step, where the float forward
    takes ``tile_plan``'s blocks."""
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
# backward: dW, db and (for a perturbed x) dx from one read of dy, g'   #
# --------------------------------------------------------------------- #

# rows of one MXU pass: dW_in sums the batch in parts of this many rows
_MXU_ROWS = 128
# rows of one group of the bias gradient's batch sum: eight 8-row vregs
_DB_ROWS = 64


def _batch_sum(du):
    """Σ_b du as a (1, block) row: each 64-row group summed vreg by vreg,
    its 8 sublanes folded by halves, the groups added in turn — the order
    of XLA's column reduce over the (256, 1,280,000) activations of the
    paper grid's cells, which made db_in before the backward kernel did."""
    tot = None
    for g0 in range(0, du.shape[0], _DB_ROWS):
        acc = du[g0:g0 + 8]
        for r in range(g0 + 8, min(g0 + _DB_ROWS, du.shape[0]), 8):
            acc = acc + du[r:r + 8]
        for h in (4, 2, 1):
            acc = acc[:h] + acc[h:2 * h]
        tot = acc if tot is None else tot + acc
    return tot


def _batch_contract(du, x_ref, acc_ref):
    """du^T·x, (block, block_f) f32, over the batch in ``_MXU_ROWS``-row
    parts: each part's contraction accumulates onto the sum of the parts
    before it, read back from the f32 scratch ``acc_ref``, as the
    batch-tiled backward's per-tile accumulator did."""
    n = du.shape[0]
    for r in range(0, n, _MXU_ROWS):
        part = jax.lax.dot_general(
            du[r:min(r + _MXU_ROWS, n)], x_ref[r:min(r + _MXU_ROWS, n), :],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        total = part if r == 0 else acc_ref[...] + part
        if r + _MXU_ROWS >= n:
            return total
        acc_ref[...] = total


def _make_bwd_kernel(g: int, block: int, nb: int, nf: int, with_dx: bool):
    def kernel(dy_ref, g_ref, x_ref, *refs):
        if with_dx:
            w_ref, dw_ref, db_ref, dx_ref, dw_acc_ref, dx_acc_ref = refs
        else:
            dw_ref, db_ref, dw_acc_ref = refs
        t = pl.program_id(0)
        kf = pl.program_id(1)
        block_f = x_ref.shape[1]
        stripe = (slice(None) if nf == 1 else
                  pl.ds(pl.multiple_of(kf * block_f, block_f), block_f))

        if with_dx:
            @pl.when(t == 0)
            def _init_dx():
                dx_acc_ref[:, stripe] = jnp.zeros(
                    (x_ref.shape[0], block_f), jnp.float32)

        def body(j, carry):
            cols = _block_cols(j, block)
            # dz0 never exists outside this register
            du = (dy_ref[:, cols].astype(jnp.float32)
                  * g_ref[:, cols].astype(jnp.float32))

            if nf == 1:
                db_ref[:, cols] = _batch_sum(du)
            else:
                @pl.when(kf == 0)
                def _db():
                    db_ref[:, cols] = _batch_sum(du)

            dum = du.astype(x_ref.dtype)
            dw_ref[cols, :] = _batch_contract(
                dum, x_ref, dw_acc_ref).astype(dw_ref.dtype)
            if with_dx:
                dx_acc_ref[:, stripe] += jax.lax.dot_general(
                    dum, w_ref[cols, :],
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, jnp.minimum(g, nb - t * g), body, 0)

        if with_dx:
            @pl.when(t == pl.num_programs(0) - 1)
            def _flush_dx():
                dx_ref[:, stripe] = dx_acc_ref[:, stripe].astype(dx_ref.dtype)
    return kernel


def fused_input_bwd(dy: jax.Array, gp: jax.Array, x: jax.Array,
                    w: jax.Array | None, *, block: int,
                    interpret: bool = False):
    """dy, g' (B, H), x (B, F_pad) [, w (H, F_pad)] → (dW (H, F_pad),
    db (H,) f32, dx (B, F_pad) or None) in ONE launch, over the forward's
    tiles: each grid step takes the whole batch and ``tile_plan``'s
    hidden blocks.  dx is made only when ``w`` is given (x perturbed)."""
    b, h = dy.shape
    f_pad = x.shape[1]
    nb = h // block
    block_f = pick_block_f(f_pad)
    nf = f_pad // block_f
    g = tile_plan(b, h, f_pad, block, itemsize=dy.dtype.itemsize).g
    with_dx = w is not None
    width = g * block
    in_specs = [
        pl.BlockSpec((b, width), lambda t, kf: (0, t)),
        pl.BlockSpec((b, width), lambda t, kf: (0, t)),
        pl.BlockSpec((b, block_f), lambda t, kf: (0, kf)),
    ]
    out_specs = [
        pl.BlockSpec((width, block_f), lambda t, kf: (t, kf)),
        pl.BlockSpec((1, width), lambda t, kf: (0, t)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((h, f_pad), dy.dtype),
        jax.ShapeDtypeStruct((1, h), jnp.float32),
    ]
    blocks = [(b, width), (b, width), (b, block_f), (width, block_f),
              (1, width)]
    scratch = [pltpu.VMEM((block, block_f), jnp.float32)]
    blocks.append((block, block_f))
    operands = [dy, gp, x]
    if with_dx:
        # dx sums over every hidden tile: its block stays resident for the
        # whole grid, so the hidden axis is a reduction axis too
        in_specs.append(pl.BlockSpec((width, block_f), lambda t, kf: (t, kf)))
        out_specs.append(pl.BlockSpec((b, f_pad), lambda t, kf: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, f_pad), dy.dtype))
        scratch.append(pltpu.VMEM((b, f_pad), jnp.float32))
        blocks += [(width, block_f), (b, f_pad), (b, f_pad)]
        operands.append(w)
    out = pl.pallas_call(
        _make_bwd_kernel(g, block, nb, nf, with_dx),
        grid=(-(-nb // g), nf),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        out_shape=out_shape,
        compiler_params=tpu_compiler_params(
            ("arbitrary" if with_dx else "parallel", "arbitrary"), *blocks),
        interpret=interpret,
        name="fused_input_bwd",
    )(*operands)
    return out[0], out[1].reshape(h), out[2] if with_dx else None
