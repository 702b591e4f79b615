"""Fused loss-head kernel: output projection (M3) + softmax cross-entropy
+ dlogits in ONE Pallas pass per direction (DESIGN.md §9).

The softmax cross-entropy runs in the epilogue of the projection while
each member's logits are still in VMEM, so the logits never reach HBM.

Tiling.  Each grid step takes the WHOLE (padded) batch and ``G``
consecutive hidden blocks of ``block_h`` lanes — about 2 MiB of ``h``,
16 blocks of 128 lanes at B = 256 — so the fixed cost of a step is
spread over enough bytes to approach the HBM bound.  Inside a step the
``G`` blocks are walked in a static loop with no branches, so the MXU
work of consecutive blocks can overlap.  A member may begin or end
anywhere in a tile and may span tiles: its logits sum in an f32 (O, B)
carry that survives across grid steps, restarted (with the member's
bias) on its first block.  The per-block member ids
(``block_segment_ids``) ride scalar prefetch, padded with −1 on both
sides, so first/last/padding are read off neighbouring ids and a last
tile that is only partly filled needs no other metadata.

Layout.  Logits are computed TRANSPOSED, ``(O, B)``: the batch is on the
lanes.  Row j of a (O, G, B) scratch holds the carry after block j, so
once the tile is done the softmax cross-entropy runs ONCE for the whole
tile over dense (G, B) arrays, one per class; only rows where a member
ends are used.

  forward   dlogits_base = (softmax(z) − onehot(target)) / B is stored
            lane-dense, (O, G·nt, B) f32, its (G, B) rows written whole
            per step; a member's row is that of its LAST block — about
            O·B·4 bytes per block in HBM, never a 128-lane padding of O.
            Each member's mean NLL and each class's batch sum of
            dlogits_base (the bias gradient's seed) are written ONCE into
            a member table (1 + O, R, 8, 128) that stays in VMEM for the
            whole grid: member m is lane m % 128 of sublane (m // 128) % 8
            of slab m // 1024, and the members ending in one tile touch at
            most two slabs.
  backward  walks the tiles and their blocks in REVERSE, so a member's
            last block comes first: there its dlogits_base rows are read
            and scaled by the member's cotangent into an (O, B) scratch,
            which every earlier block of the member then uses.  dh and
            dW_out are direct per-block writes: dW_out reduces over the
            whole batch inside one step, so it needs no accumulator.

Per-block tables the kernels need that are not scalars — the bias column
(forward) and the cotangent (backward) of each block's member — are
gathered by XLA into (nt, O, G) and (nt, 1, G) arrays (tens of kB).

Block shapes follow the TPU (8, 128) tiling rule: the last two dims of
every block are whole array dims or (8, 128)-aligned (``G`` is a
multiple of 8 unless one tile holds every block).  Padded batch rows
carry target −1 and contribute zero loss and zero dlogits.

Mixed precision: h/W_out tiles may be bf16; the logits accumulator, the
softmax/lse math, per-member losses, and dlogits_base are always f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.block_diag import tpu_compiler_params

# h bytes one grid step aims to read: enough to hide the fixed cost of a
# step behind the HBM transfer, little enough to double-buffer h and dh
TILE_BYTES = 2 * 1024 * 1024
# members in one (8, 128) slab of the member table
_SLAB = 8 * 128


def blocks_per_tile(n_blocks: int, batch: int, block_h: int,
                    itemsize: int) -> int:
    """Hidden blocks per grid step: ~TILE_BYTES of h, a multiple of 8 (the
    sublane tile of the dlogits block) unless one tile takes every block."""
    g = min(max(1, TILE_BYTES // (batch * block_h * itemsize)), _SLAB)
    if g >= n_blocks:
        return n_blocks
    return max(8, g // 8 * 8)


def _tiles(seg: jax.Array, g: int):
    """Tile count and the (nt·G + 2,) member ids with −1 before block 0 and
    after the last block: block b's id sits at b + 1, its neighbours at b
    and b + 2."""
    nb = seg.shape[0]
    nt = -(-nb // g)
    return nt, jnp.pad(seg.astype(jnp.int32), (1, nt * g - nb + 1),
                       constant_values=-1)


def _per_block(table: jax.Array, seg: jax.Array, nt: int,
               g: int) -> jax.Array:
    """A per-member (P, K) table → per-block (nt, K, G) for each block's
    member (padding blocks read member 0; the kernels skip them)."""
    idx = jnp.pad(seg.astype(jnp.int32), (0, nt * g - seg.shape[0]))
    return jnp.transpose(table[idx].reshape(nt, g, -1), (0, 2, 1))


def _block_flags(seg_ref, b):
    """(member, valid, first, last) of global block ``b``."""
    m = seg_ref[b + 1]
    valid = m >= 0
    first = jnp.logical_and(valid, seg_ref[b] != m)
    last = jnp.logical_and(valid, seg_ref[b + 2] != m)
    return m, valid, first, last


# --------------------------------------------------------------------- #
# contractions                                                          #
# --------------------------------------------------------------------- #
# All three run on the MXU at the caller's matmul precision: at O = 2 a
# VPU multiply with a lane or sublane reduce measured slower in each
# direction on a TPU v5e (PERF.md §6).

def _logits_t(w, h):
    """(O, bh) · (B, bh)ᵀ → (O, B) f32 logits, batch on the lanes."""
    return jax.lax.dot_general(w, h, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _grad_h(dl, w):
    """(O, B)ᵀ · (O, bh) → (B, bh) f32."""
    return jax.lax.dot_general(dl.astype(w.dtype), w,
                               (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _grad_w(dl, h):
    """(O, B) · (B, bh) → (O, bh) f32, the whole batch in one step."""
    return jax.lax.dot_general(dl.astype(h.dtype), h,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


# --------------------------------------------------------------------- #
# forward: projection + softmax-XE epilogue                             #
# --------------------------------------------------------------------- #

def _make_fwd_kernel(inv_b: float, g: int, block_h: int, with_dl: bool):
    def kernel(seg_ref, h_ref, w_ref, b_ref, t_ref, *out_and_scratch):
        if with_dl:
            tab_ref, dl_ref, acc_ref, z_ref = out_and_scratch
        else:
            tab_ref, acc_ref, z_ref = out_and_scratch
        t = pl.program_id(0)
        o = acc_ref.shape[0]

        @pl.when(t == 0)
        def _zero_table():
            tab_ref[...] = jnp.zeros_like(tab_ref)

        # projection, branch-free: row j of z_ref holds the running sum of
        # block j's member up to block j (plus its bias), complete where
        # block j is the member's last; the open member's sum carries to
        # the next step in acc_ref
        acc = acc_ref[...]
        flags = []
        for j in range(g):
            m, _, first, last = _block_flags(seg_ref, t * g + j)
            flags.append((m, last))
            cols = slice(j * block_h, (j + 1) * block_h)
            z = _logits_t(w_ref[:, cols], h_ref[:, cols])       # (O, B)
            acc = jnp.where(first, z + b_ref[:, j:j + 1], acc + z)
            for c in range(o):
                z_ref[c, j:j + 1, :] = acc[c:c + 1, :]
        acc_ref[...] = acc

        # softmax-XE of the whole tile at once: (G, B) per class, one row
        # per block; only member-end rows are read below and by the
        # backward, the others are never used
        tgt = t_ref[...]                                # (1, B) int32
        valid_b = (tgt >= 0).astype(jnp.float32)        # −1 marks batch pad
        zs = [z_ref[c] for c in range(o)]
        mx = zs[0]
        for z in zs[1:]:
            mx = jnp.maximum(mx, z)
        ex = [jnp.exp(z - mx) for z in zs]
        den = sum(ex[1:], ex[0])
        zt = sum((jnp.where(tgt == c, zs[c], 0.0) for c in range(1, o)),
                 jnp.where(tgt == 0, zs[0], 0.0))
        nll = (jnp.log(den) + mx - zt) * valid_b
        # every reduction keeps its operand 2-D (Mosaic lowers no rank-1
        # vector reductions)
        vals = [jnp.sum(nll, axis=1, keepdims=True) * inv_b]     # (G, 1)
        if with_dl:
            scale = valid_b * inv_b
            for c in range(o):
                dl = (ex[c] / den - (tgt == c).astype(jnp.float32)) * scale
                dl_ref[c] = dl
                vals.append(jnp.sum(dl, axis=1, keepdims=True))

        # member table: the members ending in this tile are consecutive,
        # so they fall in the first step member's slab or the next one
        sub = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
        lo = seg_ref[t * g + 1] // _SLAB
        hi = jnp.minimum(lo + 1, tab_ref.shape[1] - 1)
        at = []
        for m, last in flags:
            here = jnp.logical_and(
                jnp.logical_and(sub == (m // 128) % 8, lane == m % 128),
                last)
            at.append((jnp.logical_and(here, m // _SLAB == lo),
                       jnp.logical_and(here, m // _SLAB == hi)))
        for q, v in enumerate(vals):
            cur_lo, cur_hi = tab_ref[q, lo], tab_ref[q, hi]
            for j, (at_lo, at_hi) in enumerate(at):
                cur_lo = jnp.where(at_lo, v[j:j + 1, :], cur_lo)
                cur_hi = jnp.where(at_hi, v[j:j + 1, :], cur_hi)
            # where hi == lo both copies hold every update
            tab_ref[q, hi] = cur_hi
            tab_ref[q, lo] = cur_lo
    return kernel


def loss_head_fwd(h: jax.Array, w2: jax.Array, b2: jax.Array,
                  targets: jax.Array, seg: jax.Array, *, b_real: int,
                  block_h: int, g: int, with_dl: bool,
                  interpret: bool = False):
    """h (B, H), w2 (O, H), b2 (P, O) f32, targets (1, B) int32 (−1 = pad
    row), per-block member ids seg (H / block_h,) → per-member mean NLL
    (P,) f32 [, per-member batch sums of dlogits_base (P, O) f32,
    dlogits_base (O, nt·G, B) f32]."""
    b, _ = h.shape
    p, o = b2.shape
    nt, seg_t = _tiles(seg, g)
    tab_shape = (1 + o if with_dl else 1, -(-p // _SLAB), 8, 128)
    out_shape = [jax.ShapeDtypeStruct(tab_shape, jnp.float32)]
    out_specs = [pl.BlockSpec(tab_shape, lambda t, seg_r: (0, 0, 0, 0))]
    if with_dl:
        out_shape.append(jax.ShapeDtypeStruct((o, nt * g, b), jnp.float32))
        out_specs.append(pl.BlockSpec((o, g, b), lambda t, seg_r: (0, t, 0)))
    width = g * block_h
    out = pl.pallas_call(
        _make_fwd_kernel(1.0 / b_real, g, block_h, with_dl),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nt,),
            in_specs=[
                pl.BlockSpec((b, width), lambda t, seg_r: (0, t)),
                pl.BlockSpec((o, width), lambda t, seg_r: (0, t)),
                pl.BlockSpec((None, o, g), lambda t, seg_r: (t, 0, 0)),
                pl.BlockSpec((1, b), lambda t, seg_r: (0, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((o, b), jnp.float32),
                            pltpu.VMEM((o, g, b), jnp.float32)],
        ),
        out_shape=out_shape,
        compiler_params=tpu_compiler_params(
            ("arbitrary",),
            (b, width), (o, width), (o, g), (1, b), tab_shape, (o, g, b),
            (o, b), (o, g, b)),
        interpret=interpret,
        name="loss_head_fwd" if with_dl else "loss_head_eval",
    )(seg_t, h, w2, _per_block(b2, seg, nt, g), targets)
    tab = out[0].reshape(tab_shape[0], -1)[:, :p]
    if not with_dl:
        return tab[0]
    return tab[0], tab[1:].T, out[1]


# --------------------------------------------------------------------- #
# backward: dh and dW_out in one reverse pass                           #
# --------------------------------------------------------------------- #

def _make_bwd_kernel(g: int, block_h: int):
    def kernel(seg_ref, dper_ref, dl_ref, h_ref, w_ref, dh_ref, dw_ref,
               dlm_ref):
        t = pl.num_programs(0) - 1 - pl.program_id(0)
        o = dlm_ref.shape[0]
        for j in reversed(range(g)):
            _, _, _, last = _block_flags(seg_ref, t * g + j)
            cols = slice(j * block_h, (j + 1) * block_h)
            # a member's last block seeds its scaled dlogits (branch-free)
            d = dper_ref[:, j:j + 1]                           # (1, 1)
            for c in range(o):
                dlm_ref[c:c + 1, :] = jnp.where(
                    last, dl_ref[c, j:j + 1, :] * d, dlm_ref[c:c + 1, :])
            dl = dlm_ref[...]
            dh_ref[:, cols] = _grad_h(dl, w_ref[:, cols]).astype(
                dh_ref.dtype)
            dw_ref[:, cols] = _grad_w(dl, h_ref[:, cols]).astype(
                dw_ref.dtype)
    return kernel


def loss_head_bwd(dper: jax.Array, dl: jax.Array, h: jax.Array,
                  w2: jax.Array, seg: jax.Array, *, block_h: int, g: int,
                  interpret: bool = False):
    """Per-member cotangents dper (P,) f32, dlogits_base (O, nt·G, B) f32
    → (dh (B, H), dW_out (O, H)) in ONE launch."""
    b, hh = h.shape
    o = w2.shape[0]
    nt, seg_t = _tiles(seg, g)
    width = g * block_h

    def rev(t, seg_r):
        return nt - 1 - t

    return pl.pallas_call(
        _make_bwd_kernel(g, block_h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nt,),
            in_specs=[
                pl.BlockSpec((None, 1, g), lambda t, s: (rev(t, s), 0, 0)),
                pl.BlockSpec((o, g, b), lambda t, s: (0, rev(t, s), 0)),
                pl.BlockSpec((b, width), lambda t, s: (0, rev(t, s))),
                pl.BlockSpec((o, width), lambda t, s: (0, rev(t, s))),
            ],
            out_specs=[
                pl.BlockSpec((b, width), lambda t, s: (0, rev(t, s))),
                pl.BlockSpec((o, width), lambda t, s: (0, rev(t, s))),
            ],
            scratch_shapes=[pltpu.VMEM((o, b), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hh), h.dtype),
            jax.ShapeDtypeStruct((o, hh), w2.dtype),
        ],
        compiler_params=tpu_compiler_params(
            ("arbitrary",),
            (1, g), (o, g, b), (b, width), (o, width), (b, width),
            (o, width), (o, b)),
        interpret=interpret,
        name="loss_head_bwd",
    )(seg_t, _per_block(dper[:, None], seg, nt, g), dl, h, w2)
