"""Jit'd public wrappers around the Pallas kernels.

Handle: batch/feature padding to block multiples, dtype policy, the
custom VJPs that route each backward through its fused kernel, and the
``interpret`` switch: ``None`` (the default everywhere) compiles the
kernels with Mosaic on a TPU backend and runs the kernel body through the
Pallas interpreter on the CPU backend — where the tests validate them.  Any
other backend raises: no accelerator silently falls back to the
interpreter.  Every wrapper hands the kernels the SAME shapes on both
backends (no platform-dependent padding), so the CPU tests exercise the
block shapes the chip compiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attn as _flashk
from repro.kernels import fused_input as _fik
from repro.kernels import fused_layer as _flk
from repro.kernels import infer_head as _ihk
from repro.kernels import loss_head as _lhk
from repro.kernels import moe_gemm as _moek


def _resolve_interpret(interpret) -> bool:
    """None → from the backend: compiled on ``tpu``, interpreted on
    ``cpu``.  Any other backend raises rather than interpreting on an
    accelerator."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for 'tpu' and interpret on 'cpu'; backend "
        f"{backend!r} has neither (run with JAX_PLATFORMS=cpu or on a TPU)")


# Mosaic tiles the last dim of every block in 128-lane units
_LANES = 128


def _check_block(block: int, interpret: bool):
    """Compiled kernels tile the fused hidden axis at the population block;
    a block that is not a whole number of lane tiles cannot compile."""
    if not interpret and block % _LANES:
        raise ValueError(
            f"population block {block} is not a multiple of {_LANES}: the "
            "compiled Pallas kernels tile the hidden axis in 128-lane "
            f"blocks — pass --population-block {_LANES} (or a multiple)")


def _pad_axis(x: jax.Array, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


# --------------------------------------------------------------------- #
# fused layer: block-diag GEMM + bias + activation epilogue             #
# --------------------------------------------------------------------- #

def _bd_ids(layout, transposed: bool):
    import numpy as _np
    if transposed:
        fields = (layout.s_in_t, layout.s_w_t, layout.s_out_t,
                  layout.s_first_t, layout.s_last_t)
    else:
        fields = (layout.s_in, layout.s_w, layout.s_out,
                  layout.s_first, layout.s_last)
    return tuple(jnp.asarray(_np.asarray(f, _np.int32)) for f in fields)


def _bd_augment(wb: jax.Array, layout) -> jax.Array:
    """Append the shared identity tile used by pass-through members (not a
    parameter — its cotangent is discarded by the VJP)."""
    eye = jnp.eye(layout.block, dtype=wb.dtype)[None]
    return jnp.concatenate([wb, eye], axis=0)


def _bd_transposed_tiles(wb, layout):
    """Per-member-transposed augmented tile array (static permutation +
    per-tile transpose) — the dh weight of the fused layer's custom VJP."""
    import numpy as _np
    return jnp.transpose(
        _bd_augment(wb, layout)[_np.asarray(layout.perm_t, _np.int32)],
        (0, 2, 1))


class _StaticArray:
    """Hashable wrapper making a numpy constant usable as a jit /
    custom_vjp STATIC argument without materialising a per-element Python
    tuple (the fused hidden mask is 10^5-10^6 floats at paper scale —
    hashing the raw bytes once beats building and caching a tuple)."""
    __slots__ = ("arr", "_hash")

    def __init__(self, arr, dtype):
        self.arr = np.ascontiguousarray(np.asarray(arr, dtype))
        self.arr.setflags(write=False)
        self._hash = hash((self.arr.shape, self.arr.dtype.str,
                           self.arr.tobytes()))

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        return (isinstance(other, _StaticArray)
                and self.arr.dtype == other.arr.dtype
                and self.arr.shape == other.arr.shape
                and np.array_equal(self.arr, other.arr))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_core(h, wb, b_eff, layout, acts_s, mask_s, block_b, interpret):
    """Primal (no-grad contexts, e.g. eval): single-output kernel — the
    activation derivative is only computed when a VJP will consume it."""
    ids = _bd_ids(layout, transposed=False)
    return _flk.fused_layer_fwd(
        h, _bd_augment(wb, layout), jnp.reshape(b_eff, (1, -1)),
        jnp.asarray(mask_s.arr).reshape(1, -1), *ids,
        jnp.asarray(acts_s.arr),
        n_out_tiles=layout.n_out_tiles, n_steps=layout.n_steps,
        block=layout.block, block_b=block_b, with_deriv=False,
        interpret=interpret)


def _fused_fwd(h, wb, b_eff, layout, acts_s, mask_s, block_b, interpret):
    ids = _bd_ids(layout, transposed=False)
    y, gp = _flk.fused_layer_fwd(
        h, _bd_augment(wb, layout), jnp.reshape(b_eff, (1, -1)),
        jnp.asarray(mask_s.arr).reshape(1, -1), *ids,
        jnp.asarray(acts_s.arr),
        n_out_tiles=layout.n_out_tiles, n_steps=layout.n_steps,
        block=layout.block, block_b=block_b, with_deriv=True,
        interpret=interpret)
    return y, (h, wb, gp)


def _fused_bwd(layout, acts_s, mask_s, block_b, interpret, res, dy):
    import numpy as _np
    h, wb, gp = res
    ids_t = _bd_ids(layout, transposed=True)
    # ONE backward pass at any batch size (two-level grid: transposed param
    # step outer, batch tile inner) — dw tiles are emitted at the dx steps
    # where their (du, x) pair is already in VMEM
    dh, dwb = _flk.fused_layer_dx_dw(
        dy, gp, h, _bd_transposed_tiles(wb, layout), *ids_t,
        jnp.asarray(_np.asarray(layout.s_q_t, _np.int32)),
        n_in_tiles=layout.n_in_tiles, n_steps_t=layout.n_steps_t,
        n_param_blocks=layout.n_param_blocks, block=layout.block,
        block_b=block_b, interpret=interpret)
    # bias cotangent: one fused XLA reduce over tiles that exist anyway
    db = (dy.astype(jnp.float32) * gp.astype(jnp.float32)).sum(axis=0)
    return dh, dwb, db.astype(jnp.float32)


_fused_core.defvjp(_fused_fwd, _fused_bwd)


def fused_layer(h: jax.Array, wb: jax.Array, b_eff: jax.Array, layout,
                block_act_ids: np.ndarray, mask: np.ndarray, *,
                block_b: int = 128,
                interpret: bool | None = None) -> jax.Array:
    """Block-diagonal projection + bias + per-segment activation + padding
    mask in one Pallas pass (kernels/fused_layer.py; DESIGN.md §7);
    differentiable (fused custom VJP — ``dy·act'(z)`` forms in-register
    inside the transposed-GEMM and dw kernels); pads B.

    h (B, n_in_tiles·blk), wb (n_param_blocks, blk, blk) tile array,
    ``b_eff`` (n_out_tiles·blk,) the pass-through-gated bias, ``layout`` a
    static ``BlockDiagLayout``, ``block_act_ids`` the OUTPUT layer's
    per-block activation ids, ``mask`` its hidden mask →
    (B, n_out_tiles·blk) of ``act(h·W + b)·mask``.
    ``interpret=None`` auto-selects: compiled on TPU, interpreted on CPU.
    """
    interpret = _resolve_interpret(interpret)
    _check_block(layout.block, interpret)
    if h.shape[1] != layout.n_in_tiles * layout.block:
        raise ValueError(f"input axis {h.shape[1]} != "
                         f"{layout.n_in_tiles}×{layout.block}")
    if wb.shape != (layout.n_param_blocks, layout.block, layout.block):
        raise ValueError(f"weight tiles {wb.shape} != "
                         f"({layout.n_param_blocks}, {layout.block}, "
                         f"{layout.block})")
    h_out = layout.n_out_tiles * layout.block
    if b_eff.shape != (h_out,):
        raise ValueError(f"bias shape {b_eff.shape} != ({h_out},)")
    import numpy as _np
    s_act = _np.asarray(block_act_ids, _np.int32)[
        _np.asarray(layout.s_out, _np.int32)]
    block_b = min(block_b, max(8, 1 << (h.shape[0] - 1).bit_length()))
    hp, b0 = _pad_axis(h, 0, block_b)
    y = _fused_core(hp, wb, b_eff, layout, _StaticArray(s_act, np.int32),
                    _StaticArray(mask, np.float32), block_b, interpret)
    return y[:b0]


# Inference batch tile: forward-only launches keep no g' residual block in
# VMEM (the dominant extra buffer of the training kernels), so the batch
# tile defaults to 2× the training tile — half the grid rows per launch.
INFER_BLOCK_B = 256


def fused_layer_infer(h: jax.Array, wb: jax.Array, b_eff: jax.Array, layout,
                      block_act_ids: np.ndarray, mask: np.ndarray, *,
                      block_b: int = INFER_BLOCK_B,
                      interpret: bool | None = None) -> jax.Array:
    """Forward-only ``fused_layer``: same one-pass GEMM + bias + activation,
    but no custom_vjp is attached and the kernel runs ``with_deriv=False``
    unconditionally — a VJP traced through a serving program cannot emit a
    residual here, it fails loudly instead (DESIGN.md §10).  The freed VMEM
    pays for the bigger default batch tile."""
    interpret = _resolve_interpret(interpret)
    _check_block(layout.block, interpret)
    if h.shape[1] != layout.n_in_tiles * layout.block:
        raise ValueError(f"input axis {h.shape[1]} != "
                         f"{layout.n_in_tiles}×{layout.block}")
    if wb.shape != (layout.n_param_blocks, layout.block, layout.block):
        raise ValueError(f"weight tiles {wb.shape} != "
                         f"({layout.n_param_blocks}, {layout.block}, "
                         f"{layout.block})")
    h_out = layout.n_out_tiles * layout.block
    if b_eff.shape != (h_out,):
        raise ValueError(f"bias shape {b_eff.shape} != ({h_out},)")
    import numpy as _np
    s_act = _np.asarray(block_act_ids, _np.int32)[
        _np.asarray(layout.s_out, _np.int32)]
    block_b = min(block_b, max(8, 1 << (h.shape[0] - 1).bit_length()))
    hp, b0 = _pad_axis(h, 0, block_b)
    ids = _bd_ids(layout, transposed=False)
    y = _flk.fused_layer_fwd(
        hp, _bd_augment(wb, layout), jnp.reshape(b_eff, (1, -1)),
        jnp.asarray(_np.asarray(mask, _np.float32)).reshape(1, -1), *ids,
        jnp.asarray(s_act),
        n_out_tiles=layout.n_out_tiles, n_steps=layout.n_steps,
        block=layout.block, block_b=block_b, with_deriv=False,
        interpret=interpret)
    return y[:b0]


def fused_layer_infer_int8(h: jax.Array, wb_q: jax.Array,
                           wb_scale: jax.Array, b_eff: jax.Array, layout,
                           block_act_ids: np.ndarray, mask: np.ndarray, *,
                           block_b: int = INFER_BLOCK_B,
                           interpret: bool | None = None) -> jax.Array:
    """``fused_layer_infer`` over the int8 serve copy (DESIGN.md §12):
    consumes the packer's PRE-PACKED, identity-augmented tile array plus
    per-member-per-tile f32 scales — no per-call pack/augment of weight
    bytes, and the dequant runs inside the kernel's tile loop, so an f32
    weight array never exists in this program."""
    interpret = _resolve_interpret(interpret)
    blk = layout.block
    _check_block(blk, interpret)
    if h.shape[1] != layout.n_in_tiles * blk:
        raise ValueError(f"input axis {h.shape[1]} != "
                         f"{layout.n_in_tiles}×{blk}")
    if wb_q.dtype != jnp.int8:
        raise ValueError(f"int8 serve path got {wb_q.dtype} weight tiles")
    if wb_q.shape != (layout.n_param_blocks + 1, blk, blk):
        raise ValueError(
            f"weight tiles {wb_q.shape} != ({layout.n_param_blocks + 1}, "
            f"{blk}, {blk}) — the int8 store is pre-augmented (identity "
            "tile appended by quantize_population)")
    if wb_scale.shape != (layout.n_param_blocks + 1,):
        raise ValueError(f"scales {wb_scale.shape} != "
                         f"({layout.n_param_blocks + 1},)")
    h_out = layout.n_out_tiles * blk
    if b_eff.shape != (h_out,):
        raise ValueError(f"bias shape {b_eff.shape} != ({h_out},)")
    import numpy as _np
    s_act = _np.asarray(block_act_ids, _np.int32)[
        _np.asarray(layout.s_out, _np.int32)]
    block_b = min(block_b, max(8, 1 << (h.shape[0] - 1).bit_length()))
    hp, b0 = _pad_axis(h, 0, block_b)
    ids = _bd_ids(layout, transposed=False)
    y = _flk.fused_layer_int8_fwd(
        hp, wb_q, wb_scale.astype(jnp.float32).reshape(-1),
        jnp.reshape(b_eff, (1, -1)),
        jnp.asarray(_np.asarray(mask, _np.float32)).reshape(1, -1), *ids,
        jnp.asarray(s_act),
        n_out_tiles=layout.n_out_tiles, n_steps=layout.n_steps,
        block=blk, block_b=block_b, interpret=interpret)
    return y[:b0]


# --------------------------------------------------------------------- #
# fused input layer: dense GEMM + bias + activation epilogue            #
# --------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fin_core(x, w, b, act_ids, mask, block, interpret):
    """Primal (no-grad contexts, e.g. eval): single-output kernel.  The
    per-block activation ids and the hidden mask are OPERANDS (not static
    arguments), so a member-sharded caller can hand each shard its own
    slice (core.deep's shard_map path).  Every grid step takes the whole
    batch."""
    return _fik.fused_input_fwd(
        x, w, jnp.reshape(b, (1, -1)).astype(jnp.float32), mask, act_ids,
        block=block, block_b=x.shape[0], with_deriv=False,
        interpret=interpret)


def _fin_fwd(x, w, b, act_ids, mask, block, interpret):
    # symbolic zeros: each operand arrives with whether it is perturbed.
    # W_in is kept only for dx, which only a perturbed x needs: a training
    # step differentiates the parameters, and layer 0's input is the batch
    y, gp = _fik.fused_input_fwd(
        x.value, w.value, jnp.reshape(b.value, (1, -1)).astype(jnp.float32),
        mask.value, act_ids.value, block=block, block_b=x.value.shape[0],
        with_deriv=True, interpret=interpret)
    return y, (x.value, w.value if x.perturbed else None, gp)


def _fin_bwd(block, interpret, res, dy):
    x, w, gp = res
    dw, db, dx = _fik.fused_input_bwd(dy, gp, x, w, block=block,
                                      interpret=interpret)
    return dx, dw, db, None, None


_fin_core.defvjp(_fin_fwd, _fin_bwd, symbolic_zeros=True)


def fused_input(x: jax.Array, w_in: jax.Array, b_in: jax.Array,
                block_act_ids, mask, *, block: int,
                interpret: bool | None = None) -> jax.Array:
    """Dense input projection + bias + per-segment activation + padding
    mask in one Pallas pass (kernels/fused_input.py; DESIGN.md §9);
    differentiable (fused one-pass custom VJP: dW_in, db_in, and dx only
    where x is perturbed); pads B to 8 and F to the feature tiling.

    x (B, F), w_in (H, F) the stacked first-layer weight, ``b_in`` (H,),
    ``block_act_ids`` the first hidden layer's per-block activation ids,
    ``mask`` its hidden mask → (B, H) of ``act(x·W_in^T + b_in)·mask``;
    the two tables may be numpy constants or traced arrays (one member
    shard's slice under ``shard_map``).  Every grid step takes the whole
    batch and ``fused_input.tile_plan``'s hidden blocks.
    H must already be block-aligned (Population guarantees this).
    ``interpret=None`` auto-selects: compiled on TPU, interpreted on CPU.
    """
    interpret = _resolve_interpret(interpret)
    _check_block(block, interpret)
    h = w_in.shape[0]
    if h % block:
        raise ValueError(f"hidden axis {h} not {block}-aligned")
    if x.shape[1] != w_in.shape[1]:
        raise ValueError(f"feature axis {x.shape[1]} != {w_in.shape[1]}")
    if b_in.shape != (h,):
        raise ValueError(f"bias shape {b_in.shape} != ({h},)")
    xp, b0 = _pad_axis(x, 0, 8)
    f_pad = _fik.padded_features(x.shape[1])
    xp, _ = _pad_axis(xp, 1, f_pad)
    wp, _ = _pad_axis(w_in, 1, f_pad)
    y = _fin_core(xp, wp, b_in, jnp.asarray(block_act_ids, jnp.int32),
                  jnp.asarray(mask, jnp.float32).reshape(1, -1), block,
                  interpret)
    return y[:b0]


def fused_input_infer(x: jax.Array, w_in: jax.Array, b_in: jax.Array,
                      block_act_ids: np.ndarray, mask: np.ndarray, *,
                      block: int, block_b: int = INFER_BLOCK_B,
                      interpret: bool | None = None) -> jax.Array:
    """Forward-only ``fused_input``: no custom_vjp, ``with_deriv=False``
    unconditionally — no g' residual can be emitted, and the freed VMEM
    pays for the bigger default batch tile (DESIGN.md §10)."""
    interpret = _resolve_interpret(interpret)
    _check_block(block, interpret)
    h = w_in.shape[0]
    if h % block:
        raise ValueError(f"hidden axis {h} not {block}-aligned")
    if x.shape[1] != w_in.shape[1]:
        raise ValueError(f"feature axis {x.shape[1]} != {w_in.shape[1]}")
    if b_in.shape != (h,):
        raise ValueError(f"bias shape {b_in.shape} != ({h},)")
    block_b = min(block_b, max(8, 1 << (x.shape[0] - 1).bit_length()))
    xp, b0 = _pad_axis(x, 0, block_b)
    f_pad = _fik.padded_features(x.shape[1])
    xp, _ = _pad_axis(xp, 1, f_pad)
    wp, _ = _pad_axis(w_in, 1, f_pad)
    y = _fik.fused_input_fwd(
        xp, wp, jnp.reshape(b_in, (1, -1)).astype(jnp.float32),
        jnp.asarray(np.asarray(mask, np.float32)).reshape(1, -1),
        jnp.asarray(np.asarray(block_act_ids, np.int32)),
        block=block, block_b=block_b, with_deriv=False, interpret=interpret)
    return y[:b0]


def fused_input_infer_int8(x: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                           b_in: jax.Array, block_act_ids: np.ndarray,
                           mask: np.ndarray, *, block: int,
                           block_b: int = INFER_BLOCK_B,
                           interpret: bool | None = None) -> jax.Array:
    """``fused_input_infer`` over the int8 serve copy: ``w_q`` is stored
    PRE-PADDED to the kernel's feature tile (quantize_population), with one
    f32 scale per hidden row block dequantized inside the tile loop —
    weight bytes are never padded or upcast per call."""
    interpret = _resolve_interpret(interpret)
    _check_block(block, interpret)
    h = w_q.shape[0]
    if h % block:
        raise ValueError(f"hidden axis {h} not {block}-aligned")
    if w_q.dtype != jnp.int8:
        raise ValueError(f"int8 serve path got {w_q.dtype} input weight")
    f_pad = _fik.padded_features(x.shape[1])
    if w_q.shape[1] != f_pad:
        raise ValueError(
            f"int8 input weight has F={w_q.shape[1]}, expected the "
            f"pre-padded {f_pad} (quantize_population stores it padded)")
    if w_scale.shape != (h // block,):
        raise ValueError(f"scales {w_scale.shape} != ({h // block},)")
    if b_in.shape != (h,):
        raise ValueError(f"bias shape {b_in.shape} != ({h},)")
    block_b = min(block_b, max(8, 1 << (x.shape[0] - 1).bit_length()))
    xp, b0 = _pad_axis(x, 0, block_b)
    xp, _ = _pad_axis(xp, 1, f_pad)
    y = _fik.fused_input_int8_fwd(
        xp, w_q, w_scale.astype(jnp.float32).reshape(-1),
        jnp.reshape(b_in, (1, -1)).astype(jnp.float32),
        jnp.asarray(np.asarray(mask, np.float32)).reshape(1, -1),
        jnp.asarray(np.asarray(block_act_ids, np.int32)),
        block=block, block_b=block_b, interpret=interpret)
    return y[:b0]


# --------------------------------------------------------------------- #
# fused loss head: M3 projection + softmax-XE + dlogits                 #
# --------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _lh_core(h, w2, b2, tgt, seg, b_real, block_h, g, interpret):
    """Primal (no-grad contexts): per-member losses only; what the backward
    needs (dlogits, or the carries it recomputes them from) is only
    emitted when a VJP will consume it.  The per-block member ids are an
    OPERAND, so a member-sharded caller hands each shard its own."""
    return _lhk.loss_head_fwd(h, w2, b2, tgt, seg, b_real=b_real,
                              block_h=block_h, g=g, for_grad=False,
                              interpret=interpret)


def _lh_fwd(h, w2, b2, tgt, seg, b_real, block_h, g, interpret):
    per, dl_sum, res = _lhk.loss_head_fwd(
        h, w2, b2, tgt, seg, b_real=b_real, block_h=block_h, g=g,
        for_grad=True, interpret=interpret)
    return per, (h, w2, res, seg, dl_sum, tgt)


def _lh_bwd(b_real, block_h, g, interpret, res, dper):
    h, w2, kept, seg, dl_sum, tgt = res
    dper = dper.astype(jnp.float32)
    dh, dw = _lhk.loss_head_bwd(dper, kept, h, w2, tgt, seg, b_real=b_real,
                                block_h=block_h, g=g, interpret=interpret)
    # the bias cotangent from the batch sums the forward emitted
    db = dper[:, None] * dl_sum
    # integer targets carry a float0 cotangent
    dt = np.zeros((1, h.shape[0]), jax.dtypes.float0)
    return dh, dw, db, dt, None


_lh_core.defvjp(_lh_fwd, _lh_bwd)


def loss_head(h: jax.Array, w_out: jax.Array, b_out: jax.Array,
              targets: jax.Array, block_seg_ids, *,
              block_h: int, interpret: bool | None = None) -> jax.Array:
    """Output projection + per-member softmax cross-entropy in one Pallas
    pass (kernels/loss_head.py; DESIGN.md §9); differentiable (fused
    one-pass custom VJP emitting dh and dW_out together); pads B to 8.

    h (B, H), w_out (O, H), b_out (P, O), integer targets (B,) →
    per-member mean NLL (P,) f32 — ``per.sum()`` is the scalar training
    loss and matches the XLA log_softmax reference to f32 tolerance.
    Every grid step takes the whole batch and ``loss_head.blocks_per_tile``
    hidden blocks (about 2 MiB of h).
    ``block_seg_ids`` may be a numpy constant or a traced array (one
    member shard's local ids under ``shard_map``).
    H must already be block_h-aligned (Population guarantees this).
    ``interpret=None`` auto-selects: compiled on TPU, interpreted on CPU.
    """
    interpret = _resolve_interpret(interpret)
    _check_block(block_h, interpret)
    if h.shape[1] % block_h:
        raise ValueError(f"hidden axis {h.shape[1]} not {block_h}-aligned")
    hp, b0 = _pad_axis(h, 0, 8)
    g = _lhk.blocks_per_tile(h.shape[1] // block_h, hp.shape[0], block_h,
                             hp.dtype.itemsize)
    # pad rows carry target −1 → zero loss weight, zero dlogits
    tp = jnp.pad(targets.astype(jnp.int32).reshape(1, -1),
                 ((0, 0), (0, hp.shape[0] - b0)), constant_values=-1)
    return _lh_core(hp, w_out, b_out.astype(jnp.float32), tp,
                    jnp.asarray(block_seg_ids, jnp.int32), b0, block_h, g,
                    interpret)


def infer_head(h: jax.Array, w_out: jax.Array, b_out: jax.Array,
               block_seg_ids: np.ndarray, *, block_h: int,
               block_b: int = INFER_BLOCK_B, log_probs: bool = False,
               interpret: bool | None = None) -> jax.Array:
    """Forward-only output head: M3 projection + per-member bias (+ optional
    stable log-softmax) in one Pallas pass (kernels/infer_head.py;
    DESIGN.md §10).  NOT differentiable by design — serving programs must
    not be able to trace a residual-emitting VJP through the head.

    h (B, H), w_out (O, H), b_out (P, O) → per-member logits — or, with
    ``log_probs=True``, log-probabilities — (B, P, O) f32; pads B.
    H must already be block_h-aligned (Population guarantees this).
    ``interpret=None`` auto-selects: compiled on TPU, interpreted on CPU.
    """
    interpret = _resolve_interpret(interpret)
    _check_block(block_h, interpret)
    if h.shape[1] % block_h:
        raise ValueError(f"hidden axis {h.shape[1]} not {block_h}-aligned")
    block_b = min(block_b, max(8, 1 << (h.shape[0] - 1).bit_length()))
    hp, b0 = _pad_axis(h, 0, block_b)
    seg = jnp.asarray(np.asarray(block_seg_ids, np.int32))
    y = _ihk.infer_head_fwd(hp, w_out, b_out.astype(jnp.float32), seg,
                            b_out.shape[0], block_h=block_h, block_b=block_b,
                            log_probs=log_probs, interpret=interpret)
    # the kernel emits member-major (P, B, O)
    return jnp.transpose(y, (1, 0, 2))[:b0]


def infer_head_int8(h: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                    b_out: jax.Array, block_seg_ids: np.ndarray, *,
                    block_h: int, block_b: int = INFER_BLOCK_B,
                    log_probs: bool = False,
                    interpret: bool | None = None) -> jax.Array:
    """``infer_head`` over the int8 serve copy: one f32 scale per hidden
    tile dequantized in the projection loop."""
    interpret = _resolve_interpret(interpret)
    _check_block(block_h, interpret)
    if h.shape[1] % block_h:
        raise ValueError(f"hidden axis {h.shape[1]} not {block_h}-aligned")
    if w_q.dtype != jnp.int8:
        raise ValueError(f"int8 serve path got {w_q.dtype} head weight")
    if w_scale.shape != (h.shape[1] // block_h,):
        raise ValueError(f"scales {w_scale.shape} != "
                         f"({h.shape[1] // block_h},)")
    block_b = min(block_b, max(8, 1 << (h.shape[0] - 1).bit_length()))
    hp, b0 = _pad_axis(h, 0, block_b)
    seg = jnp.asarray(np.asarray(block_seg_ids, np.int32))
    y = _ihk.infer_head_int8_fwd(
        hp, w_q, w_scale.astype(jnp.float32).reshape(-1),
        b_out.astype(jnp.float32), seg, b_out.shape[0], block_h=block_h,
        block_b=block_b, log_probs=log_probs, interpret=interpret)
    return jnp.transpose(y, (1, 0, 2))[:b0]


# --------------------------------------------------------------------- #
# flash attention                                                        #
# --------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, scale, causal=True, window=0,
                    block_q=512, block_k=512, interpret=None):
    """Fused flash attention forward. q (B,H,Sq,dh), k/v (B,Hkv,Sk,dh).

    Backward recomputes through the exact dense/chunked XLA path
    (flash-bwd kernel is follow-up work — the forward covers serving,
    prefill, and the recompute half of remat'd training)."""
    return _flashk.flash_attention_fwd(
        q, k, v, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k,
        interpret=_resolve_interpret(interpret))


def _flash_fwd(q, k, v, scale, causal, window, block_q, block_k, interpret):
    y = flash_attention(q, k, v, scale, causal, window, block_q, block_k,
                        interpret)
    return y, (q, k, v)


def _flash_bwd(scale, causal, window, block_q, block_k, interpret, res, dy):
    from repro.kernels.ref import flash_attn_ref
    q, k, v = res
    _, vjp = jax.vjp(
        lambda qq, kk, vv: flash_attn_ref(qq, kk, vv, scale=scale,
                                          causal=causal, window=window),
        q, k, v)
    return vjp(dy)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------------------------------------------- #
# grouped GEMM                                                          #
# --------------------------------------------------------------------- #

def moe_gemm(x: jax.Array, w: jax.Array, block_expert_ids: np.ndarray, *,
             block_t: int = 128, block_d: int = 512, block_f: int = 512,
             interpret: bool | None = None) -> jax.Array:
    """Tokens-sorted-by-expert grouped GEMM. x (T, D), w (E, D, F) -> (T, F).

    T must be block_t-aligned per expert run (capacity padding upstream).
    D and F are padded here if needed.
    """
    t, d = x.shape
    e, dw, f = w.shape
    if t % block_t:
        raise ValueError(f"token axis {t} not {block_t}-aligned")
    block_d = min(block_d, d)
    block_f = min(block_f, f)
    xp, _ = _pad_axis(x, 1, block_d)
    wp, _ = _pad_axis(w, 1, block_d)
    wp, f0 = _pad_axis(wp, 2, block_f)
    ids = jnp.asarray(np.asarray(block_expert_ids, np.int32))
    y = _moek.moe_gemm(xp, wp, ids, block_t=block_t, block_d=block_d,
                       block_f=block_f,
                       interpret=_resolve_interpret(interpret))
    return y[:, :f0]
