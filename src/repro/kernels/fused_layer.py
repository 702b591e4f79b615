"""Fused population-layer kernel: block-diagonal GEMM + per-member bias +
per-segment activation in ONE Pallas pass (DESIGN.md §7).

Member m's units in layer l+1 contract ONLY member m's units in layer l,
so the fused l→l+1 weight is block-diagonal, stored as a flat array of
(block × block) tiles.  Run unfused, every mid layer would be three HBM
round trips — the GEMM writes the pre-activations z, a pass adds the bias,
another reads z+b back and writes act(z+b)·mask — and the backward would
mirror them.  Here the epilogue runs while the accumulator tile is still
in VMEM:

  forward   y  = act(z + b) · mask            (one kernel, z never in HBM)
            g' = act'(z + b) · mask           (the activation derivative,
                                               computed IN-REGISTER while z
                                               is live, emitted instead of z)
  backward  du = dy ⊙ g'  fused into ONE two-level-grid kernel — the
            transposed param step runs on the OUTER grid dimension, the
            batch tile on the INNER one, and each (step, tile) invocation
            forms du on the VPU right before both MXU contractions: the dx
            accumulation (per-batch-tile running sums in a (B, blk) f32
            scratch) and the dw parameter tile (accumulated across the
            inner batch tiles) — so neither z nor dz ever materialises in
            HBM in either direction, at ANY batch size, in a single launch.
            db = Σ_b dy·g' is one XLA fused reduce over arrays that exist
            anyway.

Grid/tile metadata is the ragged flattened step layout of
``core.population.BlockDiagLayout``; the per-step activation id (the
OUTPUT tile's segment activation) is scalar-prefetched and dispatched
through ``lax.switch`` over the kernel forms of the ten paper activations
(kernels/epilogue.py), only on the flush step of each output tile.

Mixed precision: operand tiles may be bf16 (``--compute-dtype bfloat16``);
the accumulator and the bias add are always f32 (``preferred_element_type``
+ f32 VMEM scratch), and outputs are cast back to the operand dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import tpu_compiler_params
from repro.kernels.epilogue import VAL_BRANCHES, VAL_DERIV_BRANCHES


# --------------------------------------------------------------------- #
# forward: GEMM + bias + activation epilogue                            #
# --------------------------------------------------------------------- #

def _make_fwd_kernel(with_deriv: bool):
    def kernel(ins_ref, w_ids, outs_ref, first_ref, last_ref, act_ref,
               x_ref, wb_ref, b_ref, m_ref, *out_and_scratch):
        if with_deriv:
            y_ref, g_ref, acc_ref = out_and_scratch
        else:
            y_ref, acc_ref = out_and_scratch
        s = pl.program_id(1)

        @pl.when(first_ref[s] == 1)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], wb_ref[...][0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last_ref[s] == 1)
        def _epilogue():
            u = acc_ref[...] + b_ref[...].astype(jnp.float32)
            m = m_ref[...].astype(jnp.float32)
            if with_deriv:
                y, g = jax.lax.switch(act_ref[s], VAL_DERIV_BRANCHES, u)
                y_ref[...] = (y * m).astype(y_ref.dtype)
                g_ref[...] = (g * m).astype(g_ref.dtype)
            else:
                y = jax.lax.switch(act_ref[s], VAL_BRANCHES, u)
                y_ref[...] = (y * m).astype(y_ref.dtype)
    return kernel


def fused_layer_fwd(x: jax.Array, wb: jax.Array, bias: jax.Array,
                    mask: jax.Array, s_in, s_w, s_out, s_first, s_last,
                    s_act, *, n_out_tiles: int, n_steps: int, block: int,
                    block_b: int, with_deriv: bool,
                    interpret: bool = False):
    """x (B, in_tiles·blk), wb (n_tiles, blk, blk), bias/mask (1, out·blk)
    → y (B, out_tiles·blk) [, g' (B, out_tiles·blk) when ``with_deriv``]."""
    b = x.shape[0]
    grid = (b // block_b, n_steps)
    h_out = n_out_tiles * block
    out_shape = [jax.ShapeDtypeStruct((b, h_out), x.dtype)]
    out_specs = [pl.BlockSpec(
        (block_b, block),
        lambda i, s, ins, w, outs, fr, la, act: (i, outs[s]))]
    if with_deriv:
        out_shape.append(jax.ShapeDtypeStruct((b, h_out), x.dtype))
        out_specs.append(pl.BlockSpec(
            (block_b, block),
            lambda i, s, ins, w, outs, fr, la, act: (i, outs[s])))
    y = pl.pallas_call(
        _make_fwd_kernel(with_deriv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (block_b, block),
                    lambda i, s, ins, w, outs, fr, la, act: (i, ins[s])),
                pl.BlockSpec(
                    (1, block, block),
                    lambda i, s, ins, w, outs, fr, la, act: (w[s], 0, 0)),
                pl.BlockSpec(
                    (1, block),
                    lambda i, s, ins, w, outs, fr, la, act: (0, outs[s])),
                pl.BlockSpec(
                    (1, block),
                    lambda i, s, ins, w, outs, fr, la, act: (0, outs[s])),
            ],
            out_specs=out_specs if with_deriv else out_specs[0],
            scratch_shapes=[pltpu.VMEM((block_b, block), jnp.float32)],
        ),
        out_shape=out_shape if with_deriv else out_shape[0],
        compiler_params=tpu_compiler_params(
            ("parallel", "arbitrary"),
            (block_b, block), (block, block), (1, block), (1, block),
            (block_b, block), (block_b, block), (block_b, block)),
        interpret=interpret,
        name="fused_mid_fwd" if with_deriv else "fused_mid_infer",
    )(s_in, s_w, s_out, s_first, s_last, s_act, x, wb, bias, mask)
    return y


# --------------------------------------------------------------------- #
# forward, int8 weights: in-loop dequant + GEMM + bias + activation     #
# --------------------------------------------------------------------- #

def _int8_fwd_kernel(ins_ref, w_ids, outs_ref, first_ref, last_ref, act_ref,
                     sc_ref, x_ref, wb_ref, b_ref, m_ref, y_ref, acc_ref):
    """The serving twin of ``_make_fwd_kernel(False)`` for the int8 weight
    store (DESIGN.md §12): the step loads an int8 weight tile plus its f32
    per-member-per-tile scale (scalar-prefetched whole, indexed
    ``sc_ref[w_ids[s]]`` — no per-step blocked operand) and dequantizes ON
    THE VPU right before the MXU contraction — the f32 weight tile exists
    only in registers, never in HBM.  Same grid, same blocked-operand count
    as the f32 path, same epilogue: the launch count cannot differ from the
    f32/bf16 path."""
    s = pl.program_id(1)

    @pl.when(first_ref[s] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = (wb_ref[...][0].astype(jnp.float32) * sc_ref[w_ids[s]])
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last_ref[s] == 1)
    def _epilogue():
        u = acc_ref[...] + b_ref[...].astype(jnp.float32)
        m = m_ref[...].astype(jnp.float32)
        y = jax.lax.switch(act_ref[s], VAL_BRANCHES, u)
        y_ref[...] = (y * m).astype(y_ref.dtype)


def fused_layer_int8_fwd(x: jax.Array, wb_q: jax.Array, wb_scale: jax.Array,
                         bias: jax.Array, mask: jax.Array, s_in, s_w, s_out,
                         s_first, s_last, s_act, *, n_out_tiles: int,
                         n_steps: int, block: int, block_b: int,
                         interpret: bool = False):
    """x (B, in_tiles·blk), wb_q (n_tiles, blk, blk) int8, wb_scale
    (n_tiles,) f32 scalar-prefetch, bias/mask (1, out·blk) →
    y (B, out_tiles·blk).  Forward-only by construction — there is no
    ``with_deriv`` variant."""
    b = x.shape[0]
    grid = (b // block_b, n_steps)
    h_out = n_out_tiles * block
    return pl.pallas_call(
        _int8_fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (block_b, block),
                    lambda i, s, ins, w, outs, fr, la, act, sc: (i, ins[s])),
                pl.BlockSpec(
                    (1, block, block),
                    lambda i, s, ins, w, outs, fr, la, act, sc:
                        (w[s], 0, 0)),
                pl.BlockSpec(
                    (1, block),
                    lambda i, s, ins, w, outs, fr, la, act, sc:
                        (0, outs[s])),
                pl.BlockSpec(
                    (1, block),
                    lambda i, s, ins, w, outs, fr, la, act, sc:
                        (0, outs[s])),
            ],
            out_specs=pl.BlockSpec(
                (block_b, block),
                lambda i, s, ins, w, outs, fr, la, act, sc: (i, outs[s])),
            scratch_shapes=[pltpu.VMEM((block_b, block), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h_out), x.dtype),
        compiler_params=tpu_compiler_params(
            ("parallel", "arbitrary"),
            (block_b, block), (block, block), (1, block), (1, block),
            (block_b, block), (block_b, block)),
        interpret=interpret,
        name="fused_mid_infer_int8",
    )(s_in, s_w, s_out, s_first, s_last, s_act, wb_scale, x, wb_q, bias,
      mask)


# --------------------------------------------------------------------- #
# backward: ONE two-level-grid pass — dx and dw, du = dy·g' in-register #
# --------------------------------------------------------------------- #

def _dx_dw_kernel(ins_ref, w_ids, outs_ref, first_ref, last_ref, q_ref,
                  dy_ref, g_ref, x_ref, wb_ref, dx_ref, dw_ref,
                  dx_acc_ref, dw_acc_ref):
    """ONE backward pass over a two-level grid (transposed param step s
    OUTER, batch tile i INNER): at step (s, i) the du tile (dy·g', out-tile
    space) and the x tile (= this step's dx output tile) are both live in
    VMEM, so the step emits its dw parameter tile (du^T·x, accumulated
    across the inner batch tiles in a (blk, blk) f32 scratch) alongside the
    dx accumulation — the dw sweep costs zero extra kernel launches and
    zero extra du reads at ANY batch size.

    dx state: each batch tile's running sum lives in its slice of a
    (B, blk) f32 scratch, zeroed at the first step of a reduction run; the
    running value is stored to the dx output block every step.  The block
    index (i, outs[s]) changes every step so each store is copied back to
    HBM, and since every output tile belongs to exactly ONE run per batch
    tile, the run's last (complete) store is sequentially the final writer
    of that block — partial sums written earlier are overwritten.
    Pass-through steps write the appended dummy dw slot (sliced off by the
    wrapper)."""
    s = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1)
    bb = dy_ref.shape[0]

    du = dy_ref[...] * g_ref[...]          # the VPU fusion: dz tile never
                                           # exists outside this register
    rows = pl.ds(i * bb, bb)
    prev = dx_acc_ref[rows, :]
    prev = jnp.where(first_ref[s] == 1, jnp.zeros_like(prev), prev)
    acc = prev + jax.lax.dot_general(
        du, wb_ref[...][0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dx_acc_ref[rows, :] = acc
    dx_ref[...] = acc.astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _init_dw():
        dw_acc_ref[...] = jnp.zeros_like(dw_acc_ref)

    dw_acc_ref[...] += jax.lax.dot_general(
        du, x_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == nb - 1)
    def _flush_dw():
        dw_ref[...] = dw_acc_ref[...].astype(dw_ref.dtype)[None]


def fused_layer_dx_dw(dy: jax.Array, gp: jax.Array, x: jax.Array,
                      wb_t: jax.Array, s_in_t, s_w_t, s_out_t, s_first_t,
                      s_last_t, s_q_t, *, n_in_tiles: int, n_steps_t: int,
                      n_param_blocks: int, block: int, block_b: int,
                      interpret: bool = False):
    """Single-pass backward at any batch size: → (dx, dWB) where dWB has
    the trailing dummy tile already sliced off.  Batch must be padded to a
    block_b multiple (the wrapper's ``_pad_axis`` guarantees it)."""
    b = dy.shape[0]
    if b % block_b:
        raise ValueError(
            f"fused one-pass backward needs batch padded to a block_b "
            f"multiple, got batch {b} with block_b {block_b}")
    grid = (n_steps_t, b // block_b)
    dx, dwb = pl.pallas_call(
        _dx_dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (block_b, block),
                    lambda s, i, ins, w, outs, fr, la, q: (i, ins[s])),
                pl.BlockSpec(
                    (block_b, block),
                    lambda s, i, ins, w, outs, fr, la, q: (i, ins[s])),
                pl.BlockSpec(
                    (block_b, block),
                    lambda s, i, ins, w, outs, fr, la, q: (i, outs[s])),
                pl.BlockSpec(
                    (1, block, block),
                    lambda s, i, ins, w, outs, fr, la, q: (w[s], 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec(
                    (block_b, block),
                    lambda s, i, ins, w, outs, fr, la, q: (i, outs[s])),
                pl.BlockSpec(
                    (1, block, block),
                    lambda s, i, ins, w, outs, fr, la, q: (q[s], 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((b, block), jnp.float32),
                            pltpu.VMEM((block, block), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, n_in_tiles * block), dy.dtype),
            jax.ShapeDtypeStruct((n_param_blocks + 1, block, block),
                                 dy.dtype),
        ],
        compiler_params=tpu_compiler_params(
            ("arbitrary", "arbitrary"),
            (block_b, block), (block_b, block), (block_b, block),
            (block, block), (block_b, block), (block, block),
            (b, block), (block, block)),
        interpret=interpret,
        name="fused_mid_bwd",
    )(s_in_t, s_w_t, s_out_t, s_first_t, s_last_t, s_q_t, dy, gp, x, wb_t)
    return dx, dwb[:n_param_blocks]
