"""Layered ParallelMLPs — the paper's §7/Figure 3 headline extension, as the
repo's ONE population engine.

The paper trains populations with ONE hidden layer because only the first
projection (input→hidden) is trivially fusable: every later projection must
not reduce across members.  Figure 3 sketches the fix; this module builds it
on top of the layered layout (``repro.core.population.LayeredPopulation``):

  * layer 0:            ordinary fused matmul  (H1_tot × F)       — as paper
  * layers 1..L-1:      BLOCK-DIAGONAL segment matmul: member m's units in
                        layer l+1 contract ONLY member m's units in layer l.
  * output layer:       the paper's M3 (repro.core.m3).

The engine has ONE implementation decision, ``bd_impl`` (DESIGN.md §3):

  einsum — XLA: the input layer is a dot, each mid layer a per-bucket
           batched einsum (B, n, h_in) × (n, h_out, h_in) → (B, n, h_out),
           each activation the sliced pass plus the padding mask, the head
           ``m3`` and the loss ``log_softmax``;
  fused  — Pallas: ``fused_input``, ``fused_layer`` and ``loss_head``, each
           projection with its bias, activation and mask (or softmax
           cross-entropy) in its epilogue; ``infer=True`` swaps in their
           forward-only twins, ``weights_dtype="int8"`` their int8 ones.

Members may have DIFFERENT depths: a shallow member's final activations ride
through later layers as exact identity pass-throughs (no weight, no bias, no
activation), so mixed-depth fused training still equals standalone training —
verified in tests/test_layered.py.  Per-member learning rates are free under
this layout (every parameter belongs to exactly one member): pass a (P,)
vector to ``sgd_step``/``opt_step`` or build an optimizer scale tree with
``member_lr_tree`` — and the same expansion carries ANY per-member
hyperparameter (momentum, weight decay) into the stateful optimizers, so a
population races heterogeneous training recipes, not just architectures
(``opt_step`` / ``make_population_train_step(optimizer=...)``, DESIGN.md §8).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.activations import ACTIVATIONS, apply_activations_sliced
from repro.core.m3 import m3 as _m3_apply
from repro.core.m3 import m3_infer_head, m3_infer_head_int8, m3_loss_head
from repro.core.population import LayeredPopulation, Population

# The unified engine: DeepPopulation (uniform depth, one activation per
# member) is just the degenerate LayeredPopulation.
DeepPopulation = LayeredPopulation


# ---------------------------------------------------------------------- #
# the implementation decision                                            #
# ---------------------------------------------------------------------- #

BD_CHOICES = ("einsum", "fused")


def _is_fused(bd_impl: str) -> bool:
    """``bd_impl`` → whether the run takes the fused Pallas kernels
    (``"fused"``) or the XLA ops (``"einsum"``); nothing else exists."""
    if bd_impl not in BD_CHOICES:
        raise ValueError(f"bd_impl must be one of {BD_CHOICES}, "
                         f"got {bd_impl!r}")
    return bd_impl == "fused"


# ---------------------------------------------------------------------- #
# block-diagonal mid-layer projection                                    #
# ---------------------------------------------------------------------- #

def block_diag_einsum(h: jax.Array, w_buckets, lp: LayeredPopulation,
                      l: int) -> jax.Array:
    """h (B, H_l_tot) → (B, H_{l+1}_tot) as a loop of per-bucket batched
    einsums; pass-through buckets are slice copies.  Accumulates in f32
    whatever the operand dtype (the bf16 mixed-precision policy) and
    returns the operand dtype."""
    b = h.shape[0]
    outs = []
    wi = 0
    for (m0, n, hin, hout, off_in, off_out, real) in lp.proj_buckets(l):
        if real:
            hh = h[:, off_in: off_in + n * hin].reshape(b, n, hin)
            outs.append(jnp.einsum("bnh,noh->bno", hh, w_buckets[wi],
                                   preferred_element_type=jnp.float32)
                        .astype(h.dtype).reshape(b, n * hout))
            wi += 1
        else:
            outs.append(h[:, off_in: off_in + n * hin])
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


def pack_weight_tiles(w_buckets, lp: LayeredPopulation, l: int) -> jax.Array:
    """Per-bucket (n, hout, hin) arrays → the flat (n_param_blocks, blk, blk)
    tile array consumed by the Pallas kernel (member-major, row-major over
    each member's tile grid — matching ``LayeredPopulation.bd_layout``).
    Pure reshapes/transposes, so gradients flow back to the bucket arrays."""
    blk = lp.block
    tiles = []
    wi = 0
    for (m0, n, hin, hout, off_in, off_out, real) in lp.proj_buckets(l):
        if not real:
            continue
        w = w_buckets[wi]
        wi += 1
        ob, ib = hout // blk, hin // blk
        tiles.append(w.reshape(n, ob, blk, ib, blk)
                     .transpose(0, 1, 3, 2, 4)
                     .reshape(n * ob * ib, blk, blk))
    return jnp.concatenate(tiles, axis=0)


def block_diag_fused(h: jax.Array, w_buckets, lp: LayeredPopulation, l: int,
                     *, bias: jax.Array, interpret: bool | None = None,
                     block_b: int = 128) -> jax.Array:
    """FUSED mid layer: projection + pass-through-gated bias + per-segment
    activation + padding mask in one Pallas pass (kernels/fused_layer.py,
    DESIGN.md §7) — returns layer l+1's ACTIVATIONS, so callers skip the
    separate bias add and ``_act``.  The bias stays f32 (added to the f32
    accumulator in the epilogue); operand tiles follow ``h``'s dtype."""
    from repro.kernels.ops import fused_layer  # lazy: kernels import pallas
    wb = pack_weight_tiles(w_buckets, lp, l)
    pout = lp.layer_pop(l + 1)
    b_eff = (bias.astype(jnp.float32)
             * jnp.asarray(lp.active_unit_mask(l + 1), jnp.float32))
    return fused_layer(h, wb.astype(h.dtype), b_eff, lp.bd_layout(l),
                       pout.block_act_ids, pout.hidden_mask,
                       block_b=block_b, interpret=interpret)


def block_diag_fused_infer(h: jax.Array, w_buckets, lp: LayeredPopulation,
                           l: int, *, bias: jax.Array,
                           interpret: bool | None = None,
                           block_b: int | None = None) -> jax.Array:
    """Forward-only ``block_diag_fused``: same epilogue fusion, but through
    ``ops.fused_layer_infer`` — no custom_vjp, ``with_deriv=False``, and the
    bigger inference batch tile (DESIGN.md §10)."""
    from repro.kernels.ops import INFER_BLOCK_B, fused_layer_infer  # lazy
    wb = pack_weight_tiles(w_buckets, lp, l)
    pout = lp.layer_pop(l + 1)
    b_eff = (bias.astype(jnp.float32)
             * jnp.asarray(lp.active_unit_mask(l + 1), jnp.float32))
    return fused_layer_infer(
        h, wb.astype(h.dtype), b_eff, lp.bd_layout(l),
        pout.block_act_ids, pout.hidden_mask,
        block_b=INFER_BLOCK_B if block_b is None else block_b,
        interpret=interpret)


def block_diag_fused_infer_int8(h: jax.Array, qlayer: dict,
                                lp: LayeredPopulation, l: int, *,
                                interpret: bool | None = None,
                                block_b: int | None = None) -> jax.Array:
    """``block_diag_fused_infer`` over the int8 serve copy (DESIGN.md §12).
    ``qlayer`` is one ``quantize_population`` mid entry — the PRE-PACKED,
    identity-augmented int8 tile array, its per-member-per-tile f32 scales,
    and the f32 bias — so unlike the f32/bf16 path there is no per-call
    ``pack_weight_tiles``/augment: weight bytes go straight from the int8
    store into the kernel, which dequantizes inside the tile loop."""
    from repro.kernels.ops import INFER_BLOCK_B, fused_layer_infer_int8
    pout = lp.layer_pop(l + 1)
    b_eff = (qlayer["b"].astype(jnp.float32)
             * jnp.asarray(lp.active_unit_mask(l + 1), jnp.float32))
    return fused_layer_infer_int8(
        h, qlayer["wb"], qlayer["scale"], b_eff, lp.bd_layout(l),
        pout.block_act_ids, pout.hidden_mask,
        block_b=INFER_BLOCK_B if block_b is None else block_b,
        interpret=interpret)


# ---------------------------------------------------------------------- #
# input-layer projection                                                 #
# ---------------------------------------------------------------------- #

def input_xla(x: jax.Array, w_in: jax.Array, b_in: jax.Array,
              lp: LayeredPopulation) -> jax.Array:
    """Input projection as an XLA dot (f32 accumulate) + bias + the
    per-layer ``_act`` pass — the einsum path's input layer."""
    z0 = jax.lax.dot_general(x, w_in,
                             dimension_numbers=(((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return _act(lp, 0, z0 + b_in)


def input_fused(x: jax.Array, w_in: jax.Array, b_in: jax.Array,
                lp: LayeredPopulation, *,
                interpret: bool | None = None) -> jax.Array:
    """FUSED input layer: dense GEMM + bias + per-segment activation +
    padding mask in one Pallas pass (kernels/fused_input.py, DESIGN.md §9)
    — no standalone activation pass, z0 never in HBM."""
    from repro.kernels.ops import fused_input  # lazy: kernels import pallas
    p0 = lp.layer_pop(0)
    return fused_input(x, w_in, b_in.astype(jnp.float32), p0.block_act_ids,
                       p0.hidden_mask, block=lp.block, interpret=interpret)


def input_fused_infer(x: jax.Array, w_in: jax.Array, b_in: jax.Array,
                      lp: LayeredPopulation, *,
                      interpret: bool | None = None,
                      block_b: int | None = None) -> jax.Array:
    """Forward-only ``input_fused`` through ``ops.fused_input_infer`` — no
    custom_vjp, no g' residual, bigger inference batch tile."""
    from repro.kernels.ops import INFER_BLOCK_B, fused_input_infer  # lazy
    p0 = lp.layer_pop(0)
    return fused_input_infer(
        x, w_in, b_in.astype(jnp.float32), p0.block_act_ids, p0.hidden_mask,
        block=lp.block, block_b=INFER_BLOCK_B if block_b is None else block_b,
        interpret=interpret)


def input_fused_infer_int8(x: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                           b_in: jax.Array, lp: LayeredPopulation, *,
                           interpret: bool | None = None,
                           block_b: int | None = None) -> jax.Array:
    """``input_fused_infer`` over the int8 serve copy: the pre-padded int8
    input weight + per-row-block scales (quantize_population), dequantized
    inside the kernel's feature loop."""
    from repro.kernels.ops import INFER_BLOCK_B, fused_input_infer_int8
    p0 = lp.layer_pop(0)
    return fused_input_infer_int8(
        x, w_q, w_scale, b_in.astype(jnp.float32), p0.block_act_ids,
        p0.hidden_mask, block=lp.block,
        block_b=INFER_BLOCK_B if block_b is None else block_b,
        interpret=interpret)


# ---------------------------------------------------------------------- #
# parameters                                                             #
# ---------------------------------------------------------------------- #

def init_params(key, lp: LayeredPopulation, dtype=jnp.float32) -> dict:
    """torch.nn.Linear-style init (U(±1/√fan_in), per-member fan-in), every
    parameter drawn from its OWN key.  Pass-through bias slices start (and
    stay — their gradient is masked) at zero."""
    n_mid = lp.depth - 1
    keys = jax.random.split(key, 2 * n_mid + 4)
    p0 = lp.layer_pop(0)
    bound = 1.0 / np.sqrt(lp.in_features)
    params = {
        "w_in": jax.random.uniform(keys[0], (p0.total_hidden, lp.in_features),
                                   dtype, -bound, bound),
        "b_in": jax.random.uniform(keys[1], (p0.total_hidden,), dtype,
                                   -bound, bound),
        "mid": [],
    }
    for l in range(n_mid):
        kw_, kb_ = keys[2 + 2 * l], keys[3 + 2 * l]
        pout = lp.layer_pop(l + 1)
        real_buckets = [bk for bk in lp.proj_buckets(l) if bk[6]]
        kl = jax.random.split(kw_, max(len(real_buckets), 1))
        wl = []
        for bi, (m0, n, hin, hout, off_in, off_out, real) in \
                enumerate(real_buckets):
            fan = np.array([lp.layer_width(m, l) for m in range(m0, m0 + n)],
                           np.float32)
            wl.append(jax.random.uniform(kl[bi], (n, hout, hin), dtype, -1, 1)
                      * jnp.asarray(1.0 / np.sqrt(fan), dtype)[:, None, None])
        fan_unit = np.repeat(
            np.array([lp.layer_width(m, l) for m in range(lp.num_members)],
                     np.float32),
            pout.padded_sizes)
        mask = lp.active_unit_mask(l + 1)
        params["mid"].append({
            "w": wl,
            "b": jax.random.uniform(kb_, (pout.total_hidden,), dtype, -1, 1)
            * jnp.asarray(mask / np.sqrt(fan_unit), dtype)})
    plast = lp.layer_pop(lp.depth - 1)
    fan_last = np.repeat(np.array([w[-1] for w in lp.widths], np.float32),
                         plast.padded_sizes)
    params["w_out"] = (jax.random.uniform(
        keys[-2], (lp.out_features, plast.total_hidden), dtype, -1, 1)
        * jnp.asarray(1.0 / np.sqrt(fan_last), dtype)[None, :])
    params["b_out"] = (jax.random.uniform(
        keys[-1], (lp.num_members, lp.out_features), dtype, -1, 1)
        * jnp.asarray(1.0 / np.sqrt(
            np.array([w[-1] for w in lp.widths], np.float32)), dtype)[:, None])
    return params


def abstract_params(lp: LayeredPopulation, dtype=jnp.float32):
    """Shape/dtype tree of ``init_params`` without allocating (checkpoint
    restore, dry-run costing)."""
    return jax.eval_shape(lambda k: init_params(k, lp, dtype),
                          jax.random.PRNGKey(0))


def _fill_layout(lp: LayeredPopulation,
                 lp_pad: LayeredPopulation) -> LayeredPopulation:
    """The filler-members-only layout of a ``lp.shard_pad(n)`` extension
    (validated: pads are trailing and the real prefix is untouched)."""
    if (lp_pad.num_real != lp.num_members
            or lp_pad.widths[:lp.num_members] != lp.widths
            or lp_pad.depth != lp.depth):
        raise ValueError("lp_pad is not a shard-padded extension of lp")
    return LayeredPopulation(
        lp.in_features, lp.out_features,
        lp_pad.widths[lp_pad.num_real:],
        lp_pad.activations[lp_pad.num_real:], block=lp.block)


def _concat_pad(params: dict, fp: dict, depth: int) -> dict:
    """Append a filler-members tree ``fp`` behind ``params`` on every
    member-major axis (the trailing-pad embedding shared by ``pad_params``
    and ``pad_state``)."""
    return {
        "w_in": jnp.concatenate([params["w_in"], fp["w_in"]], axis=0),
        "b_in": jnp.concatenate([params["b_in"], fp["b_in"]], axis=0),
        "mid": [{"w": list(params["mid"][l]["w"]) + list(fp["mid"][l]["w"]),
                 "b": jnp.concatenate([params["mid"][l]["b"],
                                       fp["mid"][l]["b"]], axis=0)}
                for l in range(depth - 1)],
        "w_out": jnp.concatenate([params["w_out"], fp["w_out"]], axis=1),
        "b_out": jnp.concatenate([params["b_out"], fp["b_out"]], axis=0),
    }


def pad_params(params, lp: LayeredPopulation, lp_pad: LayeredPopulation,
               key, dtype=jnp.float32) -> dict:
    """Embed ``params`` (initialised for ``lp``) into the shard-padded
    layout ``lp_pad = lp.shard_pad(n)``; filler-member parameters are drawn
    from ``key``.  Because fillers are TRAILING in every member-major axis
    and never share a bucket with real members (``proj_buckets`` pad flag),
    the real region of the result is BIT-IDENTICAL to ``params`` — a
    sharded run initialises exactly like the single-device run."""
    if lp_pad == lp:
        return params
    fill = _fill_layout(lp, lp_pad)
    return _concat_pad(params, init_params(key, fill, dtype), lp.depth)


def map_params_subtrees(tree, ref, fn, op: str = "map"):
    """Apply ``fn`` to every params-shaped subtree of an optimizer-state
    pytree — structure AND leaf shapes matching ``ref`` (a live or abstract
    ``init_params`` tree) — passing scalar leaves (step counts) through
    untouched.  This is THE structural rule for moving optimizer state
    through layout changes (``lifecycle.compact`` gathers survivors with
    it, ``pad_state`` re-embeds them), kept in one place so the two sides
    cannot drift.  Anything else fails loudly: factored moments (adafactor
    ``v_row``/``v_col``) are not member-major along a gatherable axis."""
    p_def = jax.tree_util.tree_structure(ref)
    p_shapes = [tuple(x.shape) for x in jax.tree.leaves(ref)]

    def params_like(node):
        try:
            return (jax.tree_util.tree_structure(node) == p_def
                    and [tuple(x.shape)
                         for x in jax.tree.leaves(node)] == p_shapes)
        except Exception:
            return False

    def walk(node, path):
        if params_like(node):
            return fn(node)
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, path + (i,))
                              for i, v in enumerate(node))
        if getattr(node, "ndim", None) == 0 or np.isscalar(node):
            return node
        raise ValueError(
            f"{op}: optimizer-state leaf {'/'.join(map(str, path))} is "
            "neither a scalar nor part of a params-shaped subtree (factored "
            "moments, e.g. adafactor's v_row/v_col, are not compactable "
            "member-major)")

    return walk(tree, ())


def pad_state(opt_state, lp: LayeredPopulation,
              lp_pad: LayeredPopulation):
    """Embed a (typically just-compacted) optimizer state into the
    shard-padded layout: every params-shaped subtree (SGD ``mu``, AdamW
    ``m``/``v``) gains ZERO moments for the filler members — exactly what a
    fresh ``opt.init`` of the padded params would give them, so the real
    members' trajectory is unchanged by padding — and scalar leaves (step
    counts) pass through.  Moment dtype (e.g. the bf16 state policy) is
    preserved per subtree."""
    if lp_pad == lp:
        return opt_state
    fill_abs = abstract_params(_fill_layout(lp, lp_pad))

    def pad_sub(node):
        dtype = jax.tree.leaves(node)[0].dtype
        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, dtype), fill_abs)
        return _concat_pad(node, zeros, lp.depth)

    return map_params_subtrees(opt_state, abstract_params(lp), pad_sub,
                               op="pad_state")


def grow_state(opt_state, lp: LayeredPopulation,
               lp_new: LayeredPopulation, positions,
               gather: str = "device"):
    """Splice an optimizer state into a GROWN layout (``lp_new ==
    lp.grow(...)``): survivors' moments ride through bit-exact via the
    same static-index splice as ``lifecycle.grow_params``, while the new
    members at ``positions`` get ZERO moments — exactly what a fresh
    ``opt.init`` gives a newborn, so an exploit clone restarts its
    moment estimates rather than inheriting a stale parent trajectory.
    Scalar leaves (step counts) pass through; moment dtype is preserved
    per subtree (factored adafactor states fail loudly, as everywhere)."""
    from repro.core.lifecycle import grow_params
    positions = tuple(int(p) for p in positions)
    fresh_abs = abstract_params(lp_new.subset(tuple(sorted(positions))))

    def grow_sub(node):
        dtype = jax.tree.leaves(node)[0].dtype
        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, dtype), fresh_abs)
        return grow_params(lp, lp_new, node, positions, zeros, gather=gather)

    return map_params_subtrees(opt_state, abstract_params(lp), grow_sub,
                               op="grow_state")


# ---------------------------------------------------------------------- #
# forward / loss / step                                                  #
# ---------------------------------------------------------------------- #

def _act(lp: LayeredPopulation, l: int, h: jax.Array) -> jax.Array:
    """Per-layer activation + padding mask: one XLA pass per contiguous
    activation run (``apply_activations_sliced``), then the mask."""
    pop = lp.layer_pop(l)
    h = apply_activations_sliced(h, pop.act_runs)
    return h * jnp.asarray(pop.hidden_mask, h.dtype)


def _resolve_compute_dtype(compute_dtype):
    """``None``/``"float32"`` → None (the pure-f32 fast path); anything else
    (``"bfloat16"``) → the numpy dtype operands are cast to.  Parameters,
    accumulators, loss and eval stay f32 regardless (DESIGN.md §7)."""
    if compute_dtype is None:
        return None
    cd = jnp.dtype(compute_dtype)
    return None if cd == jnp.dtype(jnp.float32) else cd


def _resolve_weights_dtype(weights_dtype):
    """``None``/``"float32"`` → None (weights consumed as stored);
    ``"int8"`` → the quantized serve-copy route (params must be a
    ``quant.quantize_population`` tree).  Anything else fails loudly —
    only int8 has fused-dequant serving kernels; a bf16 weight STORE is
    just ``tree_map(astype)`` on the params and needs no routing."""
    if weights_dtype is None:
        return None
    wd = jnp.dtype(weights_dtype)
    if wd == jnp.dtype(jnp.float32):
        return None
    if wd == jnp.dtype(jnp.int8):
        return wd
    raise ValueError(f"unsupported weights_dtype {weights_dtype!r} — only "
                     "'int8' has fused-dequant serving kernels "
                     "(DESIGN.md §12)")


def _hidden(params, x, lp: LayeredPopulation, *, bd_impl: str = "einsum",
            compute_dtype=None, infer: bool = False, weights_dtype=None):
    """Input layer + every mid layer → the last hidden activations
    (B, H_last_tot).  The shared trunk of ``forward`` and ``fused_loss``.
    ``infer=True`` swaps the fused kernels for their forward-only twins: no
    custom_vjp attached, no residual emitted, bigger batch tiles.
    ``weights_dtype="int8"`` (serving only) routes through the
    fused-dequant twins over a ``quantize_population`` tree."""
    fused = _is_fused(bd_impl)
    cd = _resolve_compute_dtype(compute_dtype)
    cast = (lambda a: a) if cd is None else (lambda a: a.astype(cd))
    if _resolve_weights_dtype(weights_dtype) is not None:
        if not infer:
            raise ValueError(
                "weights_dtype='int8' is a serving-only path — the "
                "quantized copy is not differentiable; pass infer=True")
        if not fused:
            raise ValueError(
                "weights_dtype='int8' needs the fused serving kernels "
                f"(bd_impl='fused'), got bd_impl={bd_impl!r}")
        h = input_fused_infer_int8(
            cast(x), params["w_in"], params["w_in_scale"], params["b_in"],
            lp)
        for l in range(lp.depth - 1):
            h = block_diag_fused_infer_int8(cast(h), params["mid"][l], lp, l)
        return h
    if not fused:
        h = input_xla(cast(x), cast(params["w_in"]), params["b_in"], lp)
        for l in range(lp.depth - 1):
            z = block_diag_einsum(
                cast(h), [cast(w) for w in params["mid"][l]["w"]], lp, l)
            h = z + params["mid"][l]["b"] * jnp.asarray(
                lp.active_unit_mask(l + 1), jnp.float32)
            h = _act(lp, l + 1, h)
        return h
    # bias + activation + mask live in the kernel epilogues; each output
    # is the next layer's (operand-dtype) activations
    layer_in = input_fused_infer if infer else input_fused
    layer_mid = block_diag_fused_infer if infer else block_diag_fused
    h = layer_in(cast(x), cast(params["w_in"]), params["b_in"], lp)
    for l in range(lp.depth - 1):
        h = layer_mid(cast(h), [cast(w) for w in params["mid"][l]["w"]],
                      lp, l, bias=params["mid"][l]["b"])
    return h


def forward(params, x, lp: LayeredPopulation, *, bd_impl: str = "einsum",
            compute_dtype=None, infer: bool = False,
            log_probs: bool = False, weights_dtype=None):
    """x (B, F) → logits (B, P, O) — every member an independent deep MLP.

    ``compute_dtype="bfloat16"`` applies the mixed-precision policy: matmul
    OPERANDS (activations and weights) are cast to bf16 at every projection
    boundary while accumulators run f32 (``preferred_element_type`` / f32
    VMEM scratch in the kernels), biases and the logits stay f32, and the
    f32 master parameters are untouched — gradients arrive f32.

    ``bd_impl`` is the one implementation decision (``BD_CHOICES``):
    ``"einsum"`` runs every layer as XLA ops and the head as ``m3``;
    ``"fused"`` runs the input and mid layers through the fused Pallas
    kernels (projection + bias + activation + mask in one pass, DESIGN.md
    §7, §9).

    ``infer=True`` is the serving hot path (DESIGN.md §10): every fused
    kernel is swapped for its forward-only twin (no custom_vjp, no
    residual emission, INFER_BLOCK_B batch tiles) and, on the fused path,
    the output projection is the one-launch infer-head kernel with the
    per-member bias (and, under ``log_probs=True``, the log-softmax) in its
    epilogue, making the whole forward exactly depth+1 launches
    (``launch_count.fused_infer_budget``).  Numerics match the training
    forward to f32 tolerance; the program is NOT differentiable.

    ``weights_dtype="int8"`` (serving only, DESIGN.md §12): ``params``
    must be a ``quant.quantize_population`` tree; every projection runs
    its fused-dequant int8 twin — int8 weight tiles + f32 scales are the
    ONLY weight bytes the program touches, at the same depth+1 launch
    budget.  Requires ``infer=True`` and ``bd_impl="fused"``."""
    cd = _resolve_compute_dtype(compute_dtype)
    cast = (lambda a: a) if cd is None else (lambda a: a.astype(cd))
    h = _hidden(params, x, lp, bd_impl=bd_impl, compute_dtype=compute_dtype,
                infer=infer, weights_dtype=weights_dtype)
    plast = lp.layer_pop(lp.depth - 1)
    if infer and _is_fused(bd_impl):
        # bias (and optional log-softmax) live in the kernel epilogue
        if _resolve_weights_dtype(weights_dtype) is not None:
            return m3_infer_head_int8(
                cast(h), params["w_out"], params["w_out_scale"],
                params["b_out"], plast, log_probs=log_probs)
        return m3_infer_head(cast(h), cast(params["w_out"]), params["b_out"],
                             plast, log_probs=log_probs)
    y = _m3_apply(cast(h), cast(params["w_out"]), plast)
    y = y.astype(jnp.float32) + params["b_out"][None]
    if log_probs:
        y = jax.nn.log_softmax(y, axis=-1)
    return y


def fused_loss(params, x, targets, lp: LayeredPopulation, *,
               bd_impl: str = "einsum", compute_dtype=None):
    """Summed per-member softmax cross-entropy → ``(loss, per)`` with
    ``per`` (P,) the per-member mean NLL.

    ``bd_impl="einsum"`` materialises logits via ``forward`` and runs
    log_softmax in XLA; ``"fused"`` skips ``m3`` entirely and runs
    projection + softmax-XE + dlogits in one Pallas launch per direction
    (``core.m3.m3_loss_head``, DESIGN.md §9), so a fused run's whole
    forward+backward is a fixed number of launches per layer at any batch
    size."""
    if _is_fused(bd_impl):
        cd = _resolve_compute_dtype(compute_dtype)
        cast = (lambda a: a) if cd is None else (lambda a: a.astype(cd))
        from repro.distributed.sharding import pop_axis_size
        n = pop_axis_size()
        if n > 1:
            why = _member_sharded_unsupported(lp, n)
            if why is None:
                return _fused_loss_member_sharded(params, x, targets, lp,
                                                  cast, n)
            from repro.kernels.ops import _resolve_interpret
            if not _resolve_interpret(None):
                raise NotImplementedError(why)
            # interpret-mode kernels are plain HLO that XLA partitions
        h = _hidden(params, x, lp, bd_impl=bd_impl,
                    compute_dtype=compute_dtype)
        per = m3_loss_head(cast(h), cast(params["w_out"]), params["b_out"],
                           targets, lp.layer_pop(lp.depth - 1))
        return per.sum(), per
    logits = forward(params, x, lp, bd_impl=bd_impl,
                     compute_dtype=compute_dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, targets[:, None, None].astype(jnp.int32), axis=-1)[..., 0]
    per = nll.mean(axis=0)
    return per.sum(), per


def _member_sharded_unsupported(lp: LayeredPopulation, n: int):
    """Why ``_fused_loss_member_sharded`` cannot run this layout with its
    members split ``n`` ways on the ambient mesh, or None when it can."""
    from repro.distributed.sharding import mesh_axis_sizes
    if lp.depth != 1:
        return ("member-sharded fused kernels cover depth-1 populations; "
                "train deeper layouts on a mesh with --bd-impl einsum")
    if mesh_axis_sizes().get("data", 1) > 1:
        return "member-sharded fused kernels need a mesh with data axis 1"
    pop = lp.layer_pop(0)
    seg = np.asarray(pop.block_segment_ids)
    p_loc = pop.num_members // n
    if (pop.num_members % n or len(seg) % n
            or np.any(seg.reshape(n, -1) // p_loc
                      != np.arange(n)[:, None])):
        return (f"the {n}-way member shards of {lp.describe()} do not own "
                "their hidden tiles (shard_pad the layout to the mesh)")
    return None


def _fused_loss_member_sharded(params, x, targets, lp: LayeredPopulation,
                               cast, n: int):
    """``fused_loss``'s fused head with the members split over the mesh's
    'model' axis.  XLA cannot partition a Mosaic kernel, so the fused input
    and loss-head kernels run under ``jax.shard_map``: each shard projects
    ITS members' hidden slice and scores them, with its slice of the
    per-block tables (member ids rebased to the shard).  Members are
    independent, so no collective enters the loss; the batch is whole on
    every shard.  Layouts it cannot take are named by
    ``_member_sharded_unsupported``."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import POP_AXIS
    from repro.kernels import ops

    pop = lp.layer_pop(0)
    p_loc = pop.num_members // n

    def local(x, w_in, b_in, w_out, b_out, y, act_ids, mask, seg):
        h = ops.fused_input(cast(x), cast(w_in), b_in.astype(jnp.float32),
                            act_ids, mask, block=lp.block)
        k = jax.lax.axis_index(POP_AXIS)
        return ops.loss_head(cast(h), cast(w_out), b_out, y,
                             seg - k * p_loc, block_h=lp.block)

    rows, cols = P(POP_AXIS, None), P(None, POP_AXIS)
    per = jax.shard_map(
        local, in_specs=(P(), rows, P(POP_AXIS), cols, rows, P(),
                         P(POP_AXIS), P(POP_AXIS), P(POP_AXIS)),
        out_specs=P(POP_AXIS), check_vma=False)(
        x, params["w_in"], params["b_in"], params["w_out"], params["b_out"],
        targets, jnp.asarray(pop.block_act_ids, jnp.int32),
        jnp.asarray(pop.hidden_mask, jnp.float32),
        jnp.asarray(pop.block_segment_ids, jnp.int32))
    return per.sum(), per


def member_lr_tree(lp: LayeredPopulation, lr) -> dict:
    """Per-member learning rates (P,) → a scale tree matching ``init_params``
    (every parameter belongs to exactly one member, so per-member LRs are a
    broadcast, not a loop — the paper's §7 'parallelise the learning rate').
    The same expansion serves any per-member optimizer hyperparameter: the
    result is what ``sgd(momentum=...)`` / ``adamw(weight_decay=...)``
    accept as scale trees."""
    lr = jnp.asarray(lr, jnp.float32)
    p0 = lp.layer_pop(0)
    u0 = lr[jnp.asarray(p0.segment_ids)]
    tree = {"w_in": u0[:, None], "b_in": u0, "mid": []}
    for l in range(lp.depth - 1):
        pout = lp.layer_pop(l + 1)
        wl = [lr[m0:m0 + n][:, None, None]
              for (m0, n, *_rest, real) in lp.proj_buckets(l) if real]
        tree["mid"].append({
            "w": wl, "b": lr[jnp.asarray(pout.segment_ids)]})
    plast = lp.layer_pop(lp.depth - 1)
    tree["w_out"] = lr[jnp.asarray(plast.segment_ids)][None, :]
    tree["b_out"] = lr[:, None]
    return tree


def _sgd_update(params, x, targets, lr, lp: LayeredPopulation, *,
                bd_impl: str = "einsum", compute_dtype=None):
    """The un-jitted SGD step body (shared by ``sgd_step`` and the scanned
    ``make_population_train_step``).  ``lr`` may be a scalar or a
    per-member (P,) vector.  Under ``compute_dtype="bfloat16"`` the forward
    operands run bf16 but the loss is f32, so against f32 master params the
    gradients (and the update) stay f32 — mixed precision never touches the
    optimizer math."""
    (loss, per), grads = jax.value_and_grad(fused_loss, has_aux=True)(
        params, x, targets, lp, bd_impl=bd_impl, compute_dtype=compute_dtype)
    lr = jnp.asarray(lr)
    if lr.ndim == 0:
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    else:
        scales = member_lr_tree(lp, lr)
        new = jax.tree.map(lambda p, g, s: p - s * g, params, grads, scales)
    return new, loss, per


@partial(jax.jit, static_argnames=("lp", "bd_impl", "compute_dtype"))
def sgd_step(params, x, targets, lr, lp: LayeredPopulation, *,
             bd_impl: str = "einsum", compute_dtype=None):
    """One fused SGD step.  ``lr`` may be a scalar or a per-member (P,)
    vector."""
    return _sgd_update(params, x, targets, lr, lp, bd_impl=bd_impl,
                       compute_dtype=compute_dtype)


def _opt_update(params, opt_state, x, targets, lr, opt,
                lp: LayeredPopulation, *, bd_impl: str = "einsum",
                compute_dtype=None, grad_clip=None):
    """The optimizer-generic step body (``_sgd_update``'s successor):
    fused loss + grads → optional global-norm clip → ``opt.update`` →
    ``apply_updates``, carrying the optimizer state through.

    ``opt`` is a ``repro.optim.Optimizer``; ``lr`` may be a scalar, a
    per-member (P,) vector (expanded through ``member_lr_tree`` here), or
    an already-expanded per-leaf scale tree.  With ``opt=sgd()`` (scalar
    momentum 0) the parameter update is BIT-IDENTICAL to ``_sgd_update``'s
    ``p - lr·g``: the optimizer path computes ``p + (-lr)·g``, and IEEE
    negate/multiply/subtract make the two exactly equal — regression-tested
    in tests/test_population_optim.py, which is what lets the driver run
    every optimizer through ONE engine without perturbing the plain-SGD
    trajectories (halving invariants)."""
    from repro.optim.optimizers import apply_updates, clip_by_global_norm
    (loss, per), grads = jax.value_and_grad(fused_loss, has_aux=True)(
        params, x, targets, lp, bd_impl=bd_impl, compute_dtype=compute_dtype)
    gnorm = None
    if grad_clip:
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
    if not isinstance(lr, (dict, list, tuple)):
        lr = jnp.asarray(lr)
        if lr.ndim == 1:
            lr = member_lr_tree(lp, lr)
    upd, opt_state = opt.update(grads, opt_state, params, lr)
    return apply_updates(params, upd), opt_state, loss, per, gnorm


@partial(jax.jit, static_argnames=("opt", "lp", "bd_impl", "compute_dtype",
                                   "grad_clip"))
def opt_step(params, opt_state, x, targets, lr, opt, lp: LayeredPopulation,
             *, bd_impl: str = "einsum", compute_dtype=None, grad_clip=None):
    """One fused optimizer step with state (``sgd_step``'s successor) →
    ``(params, opt_state, loss, per_member_losses, grad_norm)``;
    ``grad_norm`` is None unless ``grad_clip`` is set."""
    return _opt_update(params, opt_state, x, targets, lr, opt, lp,
                       bd_impl=bd_impl, compute_dtype=compute_dtype,
                       grad_clip=grad_clip)


def make_population_train_step(lp: LayeredPopulation, *,
                               optimizer=None,
                               grad_clip=None,
                               bd_impl: str = "einsum",
                               scan_steps: int = 1,
                               donate: bool = True,
                               donate_batch: bool = False,
                               compute_dtype=None,
                               lr_schedule=None):
    """Build the jitted multi-step population train chunk.

    Without ``optimizer`` this is the stateless plain-SGD chunk:
    ``chunk(params, xs, ys, lr) -> (params, losses, pers)``.  With an
    ``optimizer`` (a ``repro.optim.Optimizer``) the chunk carries the
    optimizer state through the scan —

      ``chunk(params, opt_state, xs, ys, lr)
          -> (params, opt_state, losses, pers, gnorms)``

    where ``gnorms`` (scan_steps,) holds each inner step's pre-clip global
    gradient norm when ``grad_clip`` is set (None otherwise).  Both params
    AND opt state are donated: at 10k members the moment trees double the
    dominant HBM resident, so reusing their buffers in place matters twice
    as much as it did for params alone.

    ``lr_schedule`` (a ``step -> multiplier`` callable, e.g.
    ``repro.optim.warmup_cosine(1.0, ...)``) threads the GLOBAL step
    through the scan as a carry: each chunk signature gains a trailing
    ``step0`` argument (the global step of the chunk's first batch —
    resume-correct because the driver passes its segment cursor) and inner
    step k trains at ``lr · lr_schedule(step0 + k)``.  ``lr`` keeps its
    scalar-or-(P,) semantics — the multiplier broadcasts, so per-member
    LRs and the schedule compose, and filler members simply ride the same
    multiplier (they are excluded from selection regardless).  With
    ``lr_schedule=None`` the signatures and the emitted program are
    EXACTLY the pre-schedule ones: the plain-SGD chunk stays bit-identical
    to the unscheduled one.

    ``donate_batch`` additionally donates the ``xs``/``ys`` slabs (only
    meaningful with ``donate``): the streaming data plane
    (``data/pipeline.py``) hands each chunk a freshly ``device_put`` slab
    that nothing else references, so XLA may reuse its buffer — at
    scan_steps×B×F float32 per chunk this keeps the double-buffered
    pipeline's device footprint at exactly two slabs.

    ``xs``/``ys`` carry a leading ``scan_steps`` axis and ``losses``
    (scan_steps,) / ``pers`` (scan_steps, P) hold every inner step's
    metrics.  The inner steps run under ONE ``lax.scan``, so the chunk
    dispatches to the device once per ``scan_steps`` optimizer steps and
    state never round-trips to host between them.  Under a mesh, sharded
    inputs keep their sharding through the scan: member-major layouts are
    collective-free, so XLA propagates the population axis end to end —
    optimizer moments included (``LayeredPopulation.opt_specs``)."""
    if scan_steps < 1:
        raise ValueError(f"scan_steps must be >= 1, got {scan_steps}")
    _is_fused(bd_impl)  # refuse an unknown implementation before tracing

    if optimizer is None:
        if grad_clip:
            raise ValueError(
                "grad_clip runs through the optimizer engine — pass "
                "optimizer= (e.g. repro.optim.sgd()) alongside it")

        if lr_schedule is None:
            def chunk(params, xs, ys, lr):
                def body(p, batch):
                    x, y = batch
                    p, loss, per = _sgd_update(
                        p, x, y, lr, lp, bd_impl=bd_impl,
                        compute_dtype=compute_dtype)
                    return p, (loss, per)
                params, (losses, pers) = jax.lax.scan(body, params, (xs, ys))
                return params, losses, pers
        else:
            def chunk(params, xs, ys, lr, step0):
                def body(carry, batch):
                    p, g = carry
                    x, y = batch
                    lr_t = jnp.asarray(lr) * lr_schedule(g)
                    p, loss, per = _sgd_update(
                        p, x, y, lr_t, lp, bd_impl=bd_impl,
                        compute_dtype=compute_dtype)
                    return (p, g + 1), (loss, per)
                (params, _), (losses, pers) = jax.lax.scan(
                    body, (params, jnp.asarray(step0, jnp.int32)), (xs, ys))
                return params, losses, pers

        dn = ((0, 1, 2) if donate_batch else (0,)) if donate else ()
        return jax.jit(chunk, donate_argnums=dn)

    if lr_schedule is None:
        def chunk(params, opt_state, xs, ys, lr):
            def body(carry, batch):
                p, st = carry
                x, y = batch
                p, st, loss, per, gnorm = _opt_update(
                    p, st, x, y, lr, optimizer, lp, bd_impl=bd_impl,
                    compute_dtype=compute_dtype, grad_clip=grad_clip)
                return (p, st), (loss, per, gnorm)
            (params, opt_state), (losses, pers, gnorms) = jax.lax.scan(
                body, (params, opt_state), (xs, ys))
            return params, opt_state, losses, pers, gnorms
    else:
        def chunk(params, opt_state, xs, ys, lr, step0):
            def body(carry, batch):
                p, st, g = carry
                x, y = batch
                mult = lr_schedule(g)
                if isinstance(lr, (dict, list, tuple)):  # scale tree
                    lr_t = jax.tree.map(lambda s: s * mult, lr)
                else:
                    lr_t = jnp.asarray(lr) * mult
                p, st, loss, per, gnorm = _opt_update(
                    p, st, x, y, lr_t, optimizer, lp, bd_impl=bd_impl,
                    compute_dtype=compute_dtype, grad_clip=grad_clip)
                return (p, st, g + 1), (loss, per, gnorm)
            (params, opt_state, _), (losses, pers, gnorms) = jax.lax.scan(
                body, (params, opt_state, jnp.asarray(step0, jnp.int32)),
                (xs, ys))
            return params, opt_state, losses, pers, gnorms

    dn = ((0, 1, 2, 3) if donate_batch else (0, 1)) if donate else ()
    return jax.jit(chunk, donate_argnums=dn)


# ---------------------------------------------------------------------- #
# member extraction (standalone baseline)                                #
# ---------------------------------------------------------------------- #

def extract_member(params, lp: LayeredPopulation, m: int) -> dict:
    """Standalone deep MLP of member m (REAL units and layers only)."""
    d = lp.member_depths[m]
    p0 = lp.layer_pop(0)
    out = {"w_in": params["w_in"][p0.member_slice(m)],
           "b_in": params["b_in"][p0.member_slice(m)],
           "mid": [],
           "activations": lp.activations[m],
           "activation": lp.activations[m][0]}
    for l in range(d - 1):
        wi = 0
        for (m0, n, hin, hout, off_in, off_out, real) in lp.proj_buckets(l):
            if m0 <= m < m0 + n:
                assert real, f"member {m} has no real projection at layer {l}"
                wm = params["mid"][l]["w"][wi][m - m0][
                    : lp.widths[m][l + 1], : lp.widths[m][l]]
                break
            if real:
                wi += 1
        bm = params["mid"][l]["b"][lp.layer_pop(l + 1).member_slice(m)]
        out["mid"].append({"w": wm, "b": bm})
    plast = lp.layer_pop(lp.depth - 1)
    out["w_out"] = params["w_out"][:, plast.member_slice(m)]
    out["b_out"] = params["b_out"][m]
    return out


def member_forward(member: dict, x):
    """Forward of one extracted member, honouring per-layer activations."""
    acts = member.get("activations") or (member["activation"],) * (
        len(member["mid"]) + 1)
    h = ACTIVATIONS[acts[0]](x @ member["w_in"].T + member["b_in"])
    for l, lay in enumerate(member["mid"]):
        h = ACTIVATIONS[acts[l + 1]](h @ lay["w"].T + lay["b"])
    return h @ member["w_out"].T + member["b_out"]
