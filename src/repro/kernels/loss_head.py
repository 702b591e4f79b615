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
tile that is only partly filled needs no other metadata.  Logits are
computed TRANSPOSED, ``(O, B)``: the batch is on the lanes, and once a
tile is projected its softmax cross-entropy runs ONCE for the whole tile;
only rows where a member ends are used.

What the backward needs depends on the class count, and so does the
body (``stores_dlogits``):

  few classes (O ≤ ``STORE_MAX_CLASSES``) — the forward STORES
            dlogits_base = (softmax(z) − onehot(target)) / B lane-dense,
            (O, G·nt, B) f32, a member's row being that of its LAST block:
            about O·B·4 bytes per block, never a 128-lane padding of O.
            The tile's softmax runs over dense (G, B) arrays, one per
            class, so the body unrolls over the classes; at two classes
            that is the fastest body on a TPU v5e (PERF.md §6).  Each
            member's mean NLL and each class's batch sum of dlogits_base
            (the bias gradient's seed) go ONCE into a member table
            (1 + O, R, 8, 128) that stays in VMEM for the whole grid:
            member m is lane m % 128 of sublane (m // 128) % 8 of slab
            m // 1024, and the members ending in one tile touch at most
            two slabs.
  many classes — the classes are an array axis: the carry after block j
            is stored whole as ``z[j]`` of a (G, O, B) scratch and the
            softmax reduces over the class axis, so no code loops over O
            and the body is the same at 9 classes as at 355 (it compiles
            in seconds at either).  Storing dlogits would cost more than
            recomputing them (1.02 GB at 100 classes and 10,000 one-block
            members), so the forward stores only the carry ENTERING each
            tile, (nt, O, B), and the backward recomputes the tile's
            logits and softmax from it (one more MXU contraction per
            block, on tiles already in VMEM).  Member tables hold K
            numbers per member, member m at lane m % 128 of slab m // 128,
            K on the sublanes; the members ending in one tile touch at
            most ``(G + 126) // 128 + 1`` slabs.

  backward  walks the tiles and their blocks in REVERSE, so a member's
            last block comes first: there its dlogits are scaled by the
            member's cotangent into an (O, B) scratch, which every
            earlier block of the member then uses, across tiles too.  dh
            and dW_out are direct per-block writes: dW_out reduces over
            the whole batch inside one step, so it needs no accumulator.

Per-block tables the kernels need that are not scalars — the bias column
(forward, and the many-class backward's recompute) and the cotangent
(backward) of each block's member — are gathered by XLA into (nt, O, G)
and (nt, 1, G) arrays (tens of kB at O = 2).

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

from repro.kernels.tiling import tile_blocks, tpu_compiler_params

# members in one (8, 128) slab of the few-class member table
_SLAB = 8 * 128
# classes up to which the forward stores the dlogits for the backward
# (``stores_dlogits``)
STORE_MAX_CLASSES = 8


def stores_dlogits(o: int) -> bool:
    """Whether the forward stores the dlogits for the backward (O·B·4 bytes
    a block, written and read again; the few-class body) or the backward
    recomputes them from the carries (one more MXU contraction a block;
    the many-class body).  At two classes the few-class body keeps a
    member-sharded step 1.5% faster on a TPU v5e, at 100 classes
    recomputing is 12% faster than storing (PERF.md §6)."""
    return o <= STORE_MAX_CLASSES


def blocks_per_tile(n_blocks: int, batch: int, block_h: int,
                    itemsize: int) -> int:
    """Hidden blocks per grid step: ``tiling.TILE_BYTES`` of h, at most
    one member-table slab, a multiple of 8 (the sublane tile of the
    dlogits block) unless one tile takes every block."""
    return tile_blocks(n_blocks, batch * block_h * itemsize, cap=_SLAB,
                       multiple=8)


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
# the entry points: the body is chosen from O                           #
# --------------------------------------------------------------------- #

def loss_head_fwd(h: jax.Array, w2: jax.Array, b2: jax.Array,
                  targets: jax.Array, seg: jax.Array, *, b_real: int,
                  block_h: int, g: int, for_grad: bool,
                  interpret: bool = False):
    """h (B, H), w2 (O, H), b2 (P, O) f32, targets (1, B) int32 (−1 = pad
    row), per-block member ids seg (H / block_h,) → per-member mean NLL
    (P,) f32 [, each member's batch sums of dlogits_base (P, O) f32, and
    what ``loss_head_bwd`` needs: a tuple, the dlogits (O, nt·G, B) f32
    at few classes, the per-block bias table (nt, O, G) and the carry
    entering each tile (nt, O, B) f32 at many]."""
    kw = dict(b_real=b_real, block_h=block_h, g=g, interpret=interpret)
    if stores_dlogits(b2.shape[1]):
        out = _stored_fwd(h, w2, b2, targets, seg, with_dl=for_grad, **kw)
        return (out[0], out[1], out[2:]) if for_grad else out
    out = _recomputed_fwd(h, w2, b2, targets, seg, for_grad=for_grad, **kw)
    return (out[0], out[1], out[2:]) if for_grad else out


def loss_head_bwd(dper: jax.Array, res: tuple, h: jax.Array,
                  w2: jax.Array, targets: jax.Array, seg: jax.Array, *,
                  b_real: int, block_h: int, g: int,
                  interpret: bool = False):
    """Per-member cotangents dper (P,) f32 and what the forward left
    (``res``) → (dh (B, H), dW_out (O, H)) in ONE launch."""
    if stores_dlogits(w2.shape[0]):
        return _stored_bwd(dper, *res, h, w2, seg, block_h=block_h, g=g,
                           interpret=interpret)
    return _recomputed_bwd(dper, *res, h, w2, targets, seg, b_real=b_real,
                           block_h=block_h, g=g, interpret=interpret)


# --------------------------------------------------------------------- #
# few classes: dlogits stored, one (G, B) array per class               #
# --------------------------------------------------------------------- #

def _make_stored_fwd_kernel(inv_b: float, g: int, block_h: int,
                            with_dl: bool):
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


def _stored_fwd(h, w2, b2, targets, seg, *, b_real: int, block_h: int,
                g: int, with_dl: bool, interpret: bool):
    """The few-class forward: → per-member mean NLL (P,) [, per-member
    batch sums of dlogits_base (P, O), dlogits_base (O, nt·G, B)]."""
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
        _make_stored_fwd_kernel(1.0 / b_real, g, block_h, with_dl),
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


def _make_stored_bwd_kernel(g: int, block_h: int):
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


def _stored_bwd(dper, dl, h, w2, seg, *, block_h: int, g: int,
                interpret: bool):
    """The few-class backward: dper (P,), dlogits_base (O, nt·G, B) →
    (dh (B, H), dW_out (O, H))."""
    b, hh = h.shape
    o = w2.shape[0]
    nt, seg_t = _tiles(seg, g)
    width = g * block_h

    def rev(t, seg_r):
        return nt - 1 - t

    return pl.pallas_call(
        _make_stored_bwd_kernel(g, block_h),
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


# --------------------------------------------------------------------- #
# many classes: the class axis whole, dlogits recomputed                #
# --------------------------------------------------------------------- #

def _put_members(tab_ref, seg_ref, t, g: int, vals):
    """Write ``vals[j]`` (K, 1) for every block j of tile t that ends a
    member m into the member table ``tab_ref`` (R, K, 128): member m is
    lane m % 128 of slab m // 128.  The members ending in one tile are
    consecutive, so they fall in the slabs from the tile's first member's
    on, at most ``(g + 126) // 128 + 1`` of them."""
    lane = jax.lax.broadcasted_iota(jnp.int32, tab_ref.shape[1:], 1)
    lo = seg_ref[t * g + 1] // 128
    ends = []
    for j, v in enumerate(vals):
        m, _, _, last = _block_flags(seg_ref, t * g + j)
        ends.append((m, jnp.logical_and(lane == m % 128, last), v))
    for s in range((g + 126) // 128 + 1):
        # past the table's end the slab index clamps: a repeated slab is
        # loaded again after its first write, so every update survives
        slab = jnp.minimum(lo + s, tab_ref.shape[0] - 1)
        cur = tab_ref[slab]
        for m, here, v in ends:
            cur = jnp.where(jnp.logical_and(here, m // 128 == slab), v, cur)
        tab_ref[slab] = cur


def _project_tile(seg_ref, h_ref, w_ref, b_ref, z_ref, acc, t, g,
                  block_h):
    """Walk the tile's blocks branch-free: ``z_ref[j]`` (O, B) gets block
    j's member's running logits up to block j (plus its bias), complete
    where block j is the member's last.  Returns the carry that leaves the
    tile (the open member's sum)."""
    for j in range(g):
        _, _, first, _ = _block_flags(seg_ref, t * g + j)
        cols = slice(j * block_h, (j + 1) * block_h)
        z = _logits_t(w_ref[:, cols], h_ref[:, cols])           # (O, B)
        acc = jnp.where(first, z + b_ref[:, j:j + 1], acc + z)
        z_ref[j] = acc
    return acc


def _softmax_tile(z, tgt):
    """Softmax cross-entropy of a tile's (G, O, B) logits, reduced over the
    class axis: → (exp(z − max), its class sum (G, 1, B), NLL (G, 1, B),
    onehot(target) (G, O, B)).  Batch pad rows (target −1) read a zero
    NLL."""
    tgt = tgt[None]                                     # (1, 1, B)
    mx = jnp.max(z, axis=1, keepdims=True)
    ex = jnp.exp(z - mx)
    den = jnp.sum(ex, axis=1, keepdims=True)
    hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) == tgt
    zt = jnp.sum(jnp.where(hit, z, 0.0), axis=1, keepdims=True)
    valid_b = (tgt >= 0).astype(jnp.float32)
    return ex, den, (jnp.log(den) + mx - zt) * valid_b, hit


def _dlogits(ex, den, hit, tgt, inv_b: float):
    """dlogits_base = (softmax − onehot(target))·valid/B of a tile,
    (G, O, B) f32; batch pad rows read zero."""
    scale = (tgt >= 0).astype(jnp.float32)[None] * inv_b       # (1, 1, B)
    return (ex / den - hit.astype(jnp.float32)) * scale


def _member_table(p: int, k: int):
    """Shape of a member table of K numbers per member, and its block."""
    shape = (-(-p // 128), k, 128)
    return shape, pl.BlockSpec(shape, lambda t, s: (0, 0, 0))


def _members(tab: jax.Array, p: int) -> jax.Array:
    """A member table (R, K, 128) → (P, K)."""
    return jnp.transpose(tab, (0, 2, 1)).reshape(-1, tab.shape[1])[:p]


def _make_recomputed_fwd_kernel(inv_b: float, g: int, block_h: int,
                                for_grad: bool):
    def kernel(seg_ref, h_ref, w_ref, b_ref, t_ref, *refs):
        if for_grad:
            tab_ref, sum_ref, carry_ref, acc_ref, z_ref = refs
        else:
            tab_ref, acc_ref, z_ref = refs
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _zero_tables():
            tab_ref[...] = jnp.zeros_like(tab_ref)
            if for_grad:
                sum_ref[...] = jnp.zeros_like(sum_ref)

        acc = acc_ref[...]
        if for_grad:
            # what the backward's recompute of this tile starts from
            carry_ref[...] = acc
        acc_ref[...] = _project_tile(seg_ref, h_ref, w_ref, b_ref, z_ref,
                                     acc, t, g, block_h)
        tgt = t_ref[...]
        ex, den, nll, hit = _softmax_tile(z_ref[...], tgt)
        per = jnp.sum(nll, axis=2, keepdims=True) * inv_b      # (G, 1, 1)
        _put_members(tab_ref, seg_ref, t, g, [per[j] for j in range(g)])
        if for_grad:
            # the bias gradient's seed: each member's batch sums of its
            # dlogits, so the backward need not write them
            sums = jnp.sum(_dlogits(ex, den, hit, tgt, inv_b), axis=2,
                           keepdims=True)                      # (G, O, 1)
            _put_members(sum_ref, seg_ref, t, g, [sums[j] for j in range(g)])
    return kernel


def _recomputed_fwd(h, w2, b2, targets, seg, *, b_real: int, block_h: int,
                    g: int, for_grad: bool, interpret: bool):
    """The many-class forward: → per-member mean NLL (P,) [, per-member
    batch sums of dlogits_base (P, O), the per-block bias table
    (nt, O, G) and the carry entering each tile (nt, O, B)]."""
    b, _ = h.shape
    p, o = b2.shape
    nt, seg_t = _tiles(seg, g)
    tab_shape, tab_spec = _member_table(p, 1)
    out_shape = [jax.ShapeDtypeStruct(tab_shape, jnp.float32)]
    out_specs = [tab_spec]
    sum_shape, sum_spec = _member_table(p, o)
    if for_grad:
        out_shape += [jax.ShapeDtypeStruct(sum_shape, jnp.float32),
                      jax.ShapeDtypeStruct((nt, o, b), jnp.float32)]
        out_specs += [sum_spec,
                      pl.BlockSpec((None, o, b), lambda t, s: (t, 0, 0))]
    width = g * block_h
    b_blocks = _per_block(b2, seg, nt, g)
    out = pl.pallas_call(
        _make_recomputed_fwd_kernel(1.0 / b_real, g, block_h, for_grad),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nt,),
            in_specs=[
                pl.BlockSpec((b, width), lambda t, s: (0, t)),
                pl.BlockSpec((o, width), lambda t, s: (0, t)),
                pl.BlockSpec((None, o, g), lambda t, s: (t, 0, 0)),
                pl.BlockSpec((1, b), lambda t, s: (0, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((o, b), jnp.float32),
                            pltpu.VMEM((g, o, b), jnp.float32)],
        ),
        out_shape=out_shape,
        compiler_params=tpu_compiler_params(
            ("arbitrary",),
            (b, width), (o, width), (o, g), (1, b), tab_shape, sum_shape,
            (o, b), (o, b), (g, o, b), (g, o, b), (g, o, b), (g, o, b)),
        interpret=interpret,
        name="loss_head_many_fwd" if for_grad else "loss_head_many_eval",
    )(seg_t, h, w2, b_blocks, targets)
    per = _members(out[0], p)[:, 0]
    if not for_grad:
        return per
    return per, _members(out[1], p), b_blocks, out[2]


def _make_recomputed_bwd_kernel(inv_b: float, g: int, block_h: int):
    def kernel(seg_ref, dper_ref, carry_ref, h_ref, w_ref, b_ref, t_ref,
               dh_ref, dw_ref, z_ref, dlm_ref):
        t = pl.num_programs(0) - 1 - pl.program_id(0)
        _project_tile(seg_ref, h_ref, w_ref, b_ref, z_ref, carry_ref[...],
                      t, g, block_h)
        tgt = t_ref[...]
        ex, den, _, hit = _softmax_tile(z_ref[...], tgt)
        z_ref[...] = _dlogits(ex, den, hit, tgt, inv_b)
        for j in reversed(range(g)):
            _, _, _, last = _block_flags(seg_ref, t * g + j)
            cols = slice(j * block_h, (j + 1) * block_h)
            # a member's last block seeds its scaled dlogits (branch-free)
            dl = jnp.where(last, z_ref[j] * dper_ref[:, j:j + 1],
                           dlm_ref[...])                        # (O, B)
            dlm_ref[...] = dl
            dh_ref[:, cols] = _grad_h(dl, w_ref[:, cols]).astype(
                dh_ref.dtype)
            dw_ref[:, cols] = _grad_w(dl, h_ref[:, cols]).astype(
                dw_ref.dtype)
    return kernel


def _recomputed_bwd(dper, b_blocks, carry, h, w2, targets, seg, *,
                    b_real: int, block_h: int, g: int, interpret: bool):
    """The many-class backward: dper (P,), the per-block bias table and
    the carry entering each tile → (dh (B, H), dW_out (O, H))."""
    b, hh = h.shape
    o = w2.shape[0]
    nt, seg_t = _tiles(seg, g)
    width = g * block_h

    def rev(t):
        return nt - 1 - t

    return pl.pallas_call(
        _make_recomputed_bwd_kernel(1.0 / b_real, g, block_h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nt,),
            in_specs=[
                pl.BlockSpec((None, 1, g), lambda t, s: (rev(t), 0, 0)),
                pl.BlockSpec((None, o, b), lambda t, s: (rev(t), 0, 0)),
                pl.BlockSpec((b, width), lambda t, s: (0, rev(t))),
                pl.BlockSpec((o, width), lambda t, s: (0, rev(t))),
                pl.BlockSpec((None, o, g), lambda t, s: (rev(t), 0, 0)),
                pl.BlockSpec((1, b), lambda t, s: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((b, width), lambda t, s: (0, rev(t))),
                pl.BlockSpec((o, width), lambda t, s: (0, rev(t))),
            ],
            scratch_shapes=[pltpu.VMEM((g, o, b), jnp.float32),
                            pltpu.VMEM((o, b), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hh), h.dtype),
            jax.ShapeDtypeStruct((o, hh), w2.dtype),
        ],
        compiler_params=tpu_compiler_params(
            ("arbitrary",),
            (1, g), (o, b), (b, width), (o, width), (o, g), (1, b),
            (b, width), (o, width), (g, o, b), (o, b), (g, o, b),
            (g, o, b)),
        interpret=interpret,
        name="loss_head_many_bwd",
    )(seg_t, _per_block(dper[:, None], seg, nt, g), carry, h, w2, b_blocks,
      targets)
