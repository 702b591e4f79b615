"""Symmetric int8 quantization: the shared scale math and the serving-plane
weight packer (DESIGN.md §12).

Two consumers share the three primitives here:

  * ``distributed.compression`` — int8 gradient all-reduce with error
    feedback (one scale per gradient leaf).  Its ``quantize_int8`` is the
    original proof of the scale math; it now composes these helpers with a
    bit-identical op sequence (regression-tested).
  * ``quantize_population`` — the serve-copy packer: converts a published
    population's f32/bf16 weights into int8 with per-member-per-tile
    symmetric scales, laid out exactly as the forward-only Pallas kernels
    consume them (pre-packed tile arrays, identity tile appended), so the
    serving plane never holds — or streams — an f32 weight copy
    (kernels/fused_input.py, fused_layer.py, infer_head.py int8 twins).

Scale granularity (why "per-member-per-tile"): every mid-layer weight tile
belongs to exactly one member, so a per-tile scale IS a per-member scale at
the finest granularity the kernel grid can index without extra metadata —
one f32 scalar rides each (blk, blk) int8 tile through the existing
scalar-prefetched step layout.  The input layer scales per hidden row
block (each owned by one member), the head per hidden tile (each owned by
one member's output rows).  Pass-through slots have no parameters: the
shared identity tile is appended UNQUANTIZED-in-effect (0/1 entries are
exact at scale 1.0).  Shard-pad fillers hold identity weights — quantized
like any member (also exact at their own scale), so a padded layout serves
unchanged.

What stays f32: biases (added to the f32 accumulator in the kernel
epilogues, never a matmul operand), the per-tile scales themselves, and
the training masters (quantization happens on a COPY at publish time —
``launch.serve_population.PopulationServer``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def symmetric_scale(x: jax.Array, axis=None, keepdims: bool = False):
    """``max|x|/127 + 1e-12`` over ``axis`` — the symmetric int8 scale.
    The 1e-12 floor keeps all-zero groups finite (they quantize to exact
    zeros)."""
    return jnp.max(jnp.abs(x), axis=axis, keepdims=keepdims) / 127.0 + 1e-12


def quantize(x: jax.Array, scale) -> jax.Array:
    """Round-to-nearest symmetric int8 in [-127, 127]."""
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)


def dequantize(q: jax.Array, scale) -> jax.Array:
    return q.astype(jnp.float32) * scale


# --------------------------------------------------------------------- #
# serve-copy packer                                                     #
# --------------------------------------------------------------------- #

def quantize_population(params, lp):
    """The int8 serve copy of a population's parameters.

    Returns a pytree the ``weights_dtype="int8"`` forward consumes
    directly (``deep.forward(infer=True, weights_dtype="int8")``):

      w_in        (H0, F_pad) int8 — pre-padded input weight
      w_in_scale  (H0/blk,)   f32  — one scale per hidden row block
      mid[l].wb     (n_param_blocks+1, blk, blk) int8 — PRE-PACKED tile
                    array (``pack_weight_tiles`` layout) with the shared
                    pass-through identity tile already appended
      mid[l].scale  (n_param_blocks+1,) f32 — per-tile scales, 1.0 for the
                    identity tile (0/1 entries quantize exactly)
      w_out       (O, H_last) int8
      w_out_scale (H_last/blk,) f32 — one scale per hidden tile
      b_in / mid[l].b / b_out — f32, untouched (bias adds run on the f32
                    accumulator in the kernel epilogues)

    Heterogeneous buckets, pass-through slots, and shard_pad fillers all
    ride the existing layout metadata — the packer only changes the bytes
    each tile stores, never which tile a step loads."""
    from repro.core.deep import pack_weight_tiles  # lazy: deep imports pallas
    from repro.kernels.fused_input import padded_features
    blk = lp.block
    f32 = jnp.float32

    w_in = params["w_in"].astype(f32)
    h0, f = w_in.shape
    s_in = symmetric_scale(w_in.reshape(h0 // blk, blk * f), axis=1)
    q_in = quantize(w_in, jnp.repeat(s_in, blk)[:, None])
    # stored pre-padded to the fused input kernel's feature tiling, so the
    # serve forward never pads weight bytes per call
    f_pad = padded_features(f)
    if f_pad != f:                       # zero columns are exact under int8
        q_in = jnp.pad(q_in, ((0, 0), (0, f_pad - f)))

    out = {"w_in": q_in, "w_in_scale": s_in,
           "b_in": params["b_in"].astype(f32), "mid": []}
    eye = jnp.eye(blk, dtype=jnp.int8)[None]
    for l in range(lp.depth - 1):
        wb = pack_weight_tiles(
            [w.astype(f32) for w in params["mid"][l]["w"]], lp, l)
        s = symmetric_scale(wb.reshape(wb.shape[0], -1), axis=1)
        q = quantize(wb, s[:, None, None])
        out["mid"].append({
            "wb": jnp.concatenate([q, eye], axis=0),
            "scale": jnp.concatenate([s, jnp.ones((1,), f32)]),
            "b": params["mid"][l]["b"].astype(f32)})

    w_out = params["w_out"].astype(f32)
    o, h_last = w_out.shape
    s_out = symmetric_scale(w_out.reshape(o, h_last // blk, blk),
                            axis=(0, 2))
    out["w_out"] = quantize(w_out, jnp.repeat(s_out, blk)[None, :])
    out["w_out_scale"] = s_out
    out["b_out"] = params["b_out"].astype(f32)
    return out


def unpack_weight_tiles(wb, lp, l: int):
    """Inverse of ``deep.pack_weight_tiles``: flat (n_param_blocks, blk,
    blk) tiles → the per-bucket (n, hout, hin) arrays.  Test/reference
    helper for the quantized serve copy."""
    blk = lp.block
    out, off = [], 0
    for (m0, n, hin, hout, off_in, off_out, real) in lp.proj_buckets(l):
        if not real:
            continue
        ob, ib = hout // blk, hin // blk
        cnt = n * ob * ib
        out.append(wb[off:off + cnt].reshape(n, ob, ib, blk, blk)
                   .transpose(0, 1, 3, 2, 4).reshape(n, hout, hin))
        off += cnt
    return out


def dequantize_population(qparams, lp):
    """The f32 params tree a quantized serve copy REPRESENTS — the exact
    numerics reference for the fused-dequant kernels: running this tree
    through the standard forward must match the int8 forward to normal
    kernel tolerance (independent of how large the quantization error
    is)."""
    blk = lp.block
    f = lp.in_features
    w_in = dequantize(qparams["w_in"][:, :f],
                      jnp.repeat(qparams["w_in_scale"], blk)[:, None])
    out = {"w_in": w_in, "b_in": qparams["b_in"], "mid": []}
    for l in range(lp.depth - 1):
        n_p = lp.bd_layout(l).n_param_blocks
        wb = dequantize(qparams["mid"][l]["wb"][:n_p],
                        qparams["mid"][l]["scale"][:n_p, None, None])
        out["mid"].append({"w": unpack_weight_tiles(wb, lp, l),
                           "b": qparams["mid"][l]["b"]})
    out["w_out"] = dequantize(qparams["w_out"],
                              jnp.repeat(qparams["w_out_scale"], blk)[None, :])
    out["b_out"] = qparams["b_out"]
    return out


def serve_copy_bytes(tree) -> int:
    """Total HBM bytes a params tree pins (the tracked serve-copy size)."""
    return int(sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree)))
