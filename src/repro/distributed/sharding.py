"""Sharding vocabulary + helpers.

Axis roles (DESIGN.md §5):
  pod    — outermost data parallelism across pods (crosses DCI)
  data   — in-pod data parallelism; params/optimizer FSDP-sharded over it
  model  — tensor/expert/sequence-parallel axis (TP/EP/SP); also the
           population axis for ParallelMLP training (zero-collective)

Specs are written against the FULL axis set; :func:`constrain` and
:func:`filter_spec` drop axes that the ambient mesh doesn't have, so the
same model code runs on (data, model), (pod, data, model) and single-device
CPU without edits.  Axes whose dim size doesn't divide are also dropped
(GSPMD requires even sharding for explicit constraints; uneven cases —
batch=1 long_500k decode — degrade to replication, which is correct, just
not distributed).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import set_mesh
from jax.sharding import Mesh, NamedSharding, get_abstract_mesh
from jax.sharding import PartitionSpec as P

# canonical spec fragments
BATCH_AXES = ("pod", "data")        # batch dim shards over both DP axes
FSDP_AXIS = "data"                  # parameter sharding (ZeRO-3 style)
TP_AXIS = "model"                   # tensor/expert/sequence parallel
POP_AXIS = "model"                  # population members (paper's axis)

# Megatron-style inner-dim TP is applied only to projections at least this
# wide: for big layers it shrinks weight-grad buffers/all-reduces by the TP
# degree (nemotron: 3× on the collective term), but for small layers the
# AG/RS transitions cost more than the dW savings (qwen3 regressed 28% when
# constrained unconditionally — §Perf hillclimb, refuted-then-refined).
TP_INNER_MIN_COLS = 8192


def mesh_axis_sizes() -> dict:
    mesh = get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return {}
    return dict(mesh.shape)


def filter_spec(spec: P, dims=None) -> P:
    """Drop mesh axes that don't exist; optionally check divisibility against
    ``dims`` (the tensor shape) and drop non-dividing axes.

    On a multi-pod mesh, a bare 'data' entry expands to ('pod','data') —
    hybrid FSDP: parameter/gradient/optimizer shards span pods (ZeRO across
    DCI), halving per-chip state on the 2-pod mesh (§Perf iteration 4).
    Specs that already mention 'pod' (batch dims) are left as written."""
    sizes = mesh_axis_sizes()
    if "pod" in sizes and not _mentions_pod(spec):
        spec = P(*(_expand_data(e) for e in spec))

    def ax_size(e):
        if isinstance(e, (tuple, list)):
            out = 1
            for a in e:
                out *= sizes.get(a, 1)
            return out
        return sizes.get(e, 1)

    def filt(i, e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in sizes)
        else:
            kept = (e,) if e in sizes else ()
        if not kept:
            return None
        if dims is not None:
            total = 1
            for a in kept:
                total *= sizes[a]
            if dims[i] % total != 0:
                return None
        return kept if len(kept) > 1 else kept[0]

    return P(*(filt(i, e) for i, e in enumerate(spec)))


def _mentions_pod(spec: P) -> bool:
    for e in spec:
        if e == "pod" or (isinstance(e, (tuple, list)) and "pod" in e):
            return True
    return False


def _expand_data(e):
    if e == "data":
        return ("pod", "data")
    if isinstance(e, (tuple, list)) and "data" in e and "pod" not in e:
        return tuple(a for a in e) + ("pod",)
    return e


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint that degrades gracefully: no mesh → no-op;
    missing/non-dividing axes → dropped."""
    sizes = mesh_axis_sizes()
    if not sizes:
        return x
    return jax.lax.with_sharding_constraint(x, filter_spec(spec, x.shape))


def logical_to_sharding(spec_tree, mesh: Mesh, shape_tree):
    """Spec tree + mesh + abstract shapes -> NamedSharding tree (axes
    filtered per-leaf for existence and divisibility)."""
    def leaf(spec, shp):
        with set_mesh(mesh):
            f = filter_spec(spec, shp.shape)
        return NamedSharding(mesh, f)
    return jax.tree.map(leaf, spec_tree, shape_tree,
                        is_leaf=lambda s: isinstance(s, P))


def stack_spec(spec_tree):
    """Prepend a replicated leading (layer) axis to every leaf spec — the
    spec-side mirror of vmapping an init over a stacked layer group."""
    return jax.tree.map(lambda s: P(None, *s),
                        spec_tree, is_leaf=lambda s: isinstance(s, P))


# canonical activation specs
ACT_RESIDUAL = P(BATCH_AXES, TP_AXIS, None)   # (B, S/model, D): SP residual
ACT_FULL_SEQ = P(BATCH_AXES, None, None)      # (B, S, D) gathered
ACT_HEADS = P(BATCH_AXES, None, TP_AXIS, None)          # (B, S, H/model, dh)
ACT_DECODE = P(BATCH_AXES, None, None)        # (B, 1, D)

# ------------------------------------------------------------------ #
# population specs (the paper's member axis; DESIGN.md §5)           #
# ------------------------------------------------------------------ #
# Fused population tensors are member-major: the fused hidden axis, the
# per-bucket member axis, and the (P, O) output-bias member axis all shard
# over POP_AXIS with ZERO cross-member collectives (members are
# independent by construction).  Logits carry the member axis at dim 1.
POP_HIDDEN = P(POP_AXIS)                      # (H_tot,) fused hidden
POP_BUCKET = P(POP_AXIS, None, None)          # (n, h_out, h_in) bucket stack
POP_LOGITS = P(BATCH_AXES, POP_AXIS, None)    # (B, P, O) per-member logits
POP_MEMBER = P(POP_AXIS)                      # (P,) per-member reductions
# Population train batches are (scan, B, ...): the scan axis stays on every
# device (each inner step consumes one slice), the BATCH axis shards over
# the data axes — population runs stop replicating their batches to the
# whole mesh.  GSPMD inserts the per-member loss-mean psum over 'data'.
POP_BATCH_X = P(None, BATCH_AXES, None)       # (scan, B, F) features
POP_BATCH_Y = P(None, BATCH_AXES)             # (scan, B) targets


def pop_axis_size(mesh=None) -> int:
    """Size of the population ('model') axis — of ``mesh`` if given, else of
    the ambient mesh; 1 when unmeshed.  The member-count/hidden-axis
    divisor that ``LayeredPopulation.shard_pad`` must satisfy."""
    if mesh is not None:
        return int(dict(mesh.shape).get(POP_AXIS, 1))
    return int(mesh_axis_sizes().get(POP_AXIS, 1))


def population_batch_shardings(mesh, batch_size: int):
    """NamedShardings for a population train chunk's ``(xs, ys)`` inputs
    (leading scan axis, then batch): the batch axis shards over the mesh's
    data axes, FALLING BACK to replication when ``batch_size`` doesn't
    divide them (``filter_spec`` drops the non-dividing axes, the
    documented degradation).  The specs are shape-agnostic in the leading
    scan axis, so one sharding pair serves full and tail chunks."""
    with set_mesh(mesh):
        fx = filter_spec(POP_BATCH_X, (1, batch_size, 1))
        fy = filter_spec(POP_BATCH_Y, (1, batch_size))
    return NamedSharding(mesh, fx), NamedSharding(mesh, fy)


def population_shardings(layout, mesh, dtype=None):
    """``layout.param_specs()`` + mesh → NamedSharding tree for the layout's
    parameter tree (per-leaf axis filtering handles buckets whose member
    run doesn't divide the axis — those replicate)."""
    import jax.numpy as jnp

    from repro.core.deep import abstract_params
    abs_p = abstract_params(layout, dtype or jnp.float32)
    return logical_to_sharding(layout.param_specs(), mesh, abs_p)


def population_opt_shardings(layout, opt, mesh, dtype=None):
    """``layout.opt_specs(opt)`` + mesh → NamedSharding tree for the
    optimizer STATE of training this layout with ``opt`` (a
    ``repro.optim.Optimizer``).  Every state leaf inherits the sharding of
    the parameter it tracks, so this is what born-sharded ``opt.init``
    out_shardings, rung-boundary ``device_put``s of compacted moments, and
    sharded opt-state restores all run through."""
    import jax
    import jax.numpy as jnp

    from repro.core.deep import abstract_params
    abs_st = jax.eval_shape(opt.init,
                            abstract_params(layout, dtype or jnp.float32))
    return logical_to_sharding(layout.opt_specs(opt, dtype), mesh, abs_st)


def population_state_shardings(layout, opt, mesh, dtype=None):
    """``(params, opt_state)`` NamedSharding pair for one layout — the
    rung-boundary bundle: every layout change (compact → re-pad, grow
    splice, constant-size refill) device_puts or out_shardings BOTH trees
    against the same mesh, so the driver fetches them together instead of
    re-deriving each side separately (and possibly against different
    meshes)."""
    return (population_shardings(layout, mesh, dtype),
            population_opt_shardings(layout, opt, mesh, dtype))
