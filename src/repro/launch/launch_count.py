"""Static kernel-launch counting + the per-step launch budget (DESIGN.md §9).

WHY jaxpr walking and not HLO: the CPU container runs every Pallas kernel
in interpret mode, where ``pallas_call`` lowers to ordinary XLA ops — the
compiled module contains no custom-calls to count.  The jaxpr, traced
BEFORE lowering, still carries one ``pallas_call`` equation per launch
site regardless of backend, so the count measured here in CI is exactly
the count a TPU run dispatches.  Sub-jaxprs (custom_vjp branches, pjit
bodies, cond/scan/while) are walked recursively; a ``scan`` multiplies its
body count by the trip length, so a scanned train chunk reports
launches-per-chunk (divide by ``scan_steps`` for per-step numbers).

The budget itself: the fused population path runs each direction of each
layer as exactly ONE launch — input layer, depth−1 mid layers, loss head —
so a train step costs 2·(depth+1) launches at ANY batch size.  The
two-level-grid backward (kernels/fused_layer.py) is what removed the batch
dependence; ``fused_step_budget`` is the committed invariant that
tests/test_launch_budget.py enforces against regressions.
"""
from __future__ import annotations

import jax


def _sub_jaxprs(val):
    """Jaxpr-like values reachable from an eqn param (Jaxpr, ClosedJaxpr,
    or containers of them) — duck-typed to survive jax version drift."""
    if hasattr(val, "eqns"):                 # Jaxpr
        return [val]
    if hasattr(val, "jaxpr"):                # ClosedJaxpr
        return [val.jaxpr]
    if isinstance(val, (tuple, list)):
        out = []
        for v in val:
            out.extend(_sub_jaxprs(v))
        return out
    return []


def count_jaxpr_launches(jaxpr) -> int:
    """Number of ``pallas_call`` equations in a (possibly nested) jaxpr,
    loop-weighted: a ``scan`` body counts ``length`` times."""
    n = 0
    for eqn in jaxpr.eqns:
        mult = 1
        if eqn.primitive.name == "scan":
            mult = int(eqn.params.get("length", 1))
        if eqn.primitive.name == "pallas_call":
            n += 1
        inner = 0
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                inner += count_jaxpr_launches(sub)
        n += mult * inner
    return n


def count_pallas_launches(fn, *args, **kwargs) -> int:
    """Kernel launches one call of ``fn(*args, **kwargs)`` dispatches."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return count_jaxpr_launches(closed.jaxpr)


def phase_launches(loss_fn, *args) -> dict:
    """Launches of a scalar-valued ``loss_fn`` split by phase:
    ``{"fwd", "bwd", "total"}`` where ``total`` covers one
    ``jax.grad(loss_fn)`` evaluation (VJP-forward + backward) and ``bwd``
    is ``total − fwd``.  Every kernel here launches once in its primal and
    once in its VJP-forward variant, so the subtraction is exact."""
    fwd = count_pallas_launches(loss_fn, *args)
    total = count_pallas_launches(jax.grad(loss_fn), *args)
    return {"fwd": fwd, "bwd": total - fwd, "total": total}


def fused_step_budget(depth: int) -> dict:
    """The §9 invariant for the fully fused path (``bd_impl="fused"``):
    one launch per layer per direction —
    input + (depth−1) mids + loss head — independent of batch size."""
    per_dir = depth + 1
    return {"fwd": per_dir, "bwd": per_dir, "total": 2 * per_dir}


def fused_infer_budget(depth: int) -> dict:
    """The §10 invariant for the forward-only serving path
    (``forward(infer=True)`` with fused routing): input + (depth−1) mids +
    infer head = depth+1 launches per request batch — half the train step,
    no backward phase to budget, independent of batch size."""
    return {"fwd": depth + 1, "total": depth + 1}


def max_eqn_outputs(fn, *args, primitive: str = "pallas_call",
                    **kwargs) -> int:
    """Largest number of outputs any ``primitive`` equation in ``fn``'s
    (recursively walked) jaxpr carries.  The §10 no-residual assertion:
    a forward-only program's pallas_calls are all single-output — a 2 here
    means some kernel still emits a residual (g'/dlogits) buffer."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)

    def walk(jaxpr) -> int:
        worst = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == primitive:
                worst = max(worst, len(eqn.outvars))
            for val in eqn.params.values():
                for sub in _sub_jaxprs(val):
                    worst = max(worst, walk(sub))
        return worst

    return walk(closed.jaxpr)
