"""Model selection over a trained population (paper §5: "perform model
selection in the large pool of trained MLPs").

Works over BOTH layouts — the single-layer ``Population`` and the layered
engine's ``LayeredPopulation`` — dispatching forward/extract to the matching
module, so architecture search over mixed-depth pools uses the same three
calls (evaluate → select → leaderboard) as the paper's single-layer grid.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import deep as _deep
from repro.core import parallel_mlp as _pmlp
from repro.core.parallel_mlp import member_accuracy, member_losses
from repro.core.population import LayeredPopulation, Population


def _forward(params, x, layout, **fw):
    if isinstance(layout, LayeredPopulation):
        return _deep.forward(params, x, layout, **fw)
    if fw.pop("infer", False):
        raise ValueError("infer=True eval routes through the layered "
                         "engine — single-layer Population has no "
                         "forward-only kernel path")
    return _pmlp.forward(params, x, layout, **fw)


def extract_member(params, layout, m: int) -> dict:
    """Standalone params of member m, whichever layout trained them."""
    if isinstance(layout, LayeredPopulation):
        return _deep.extract_member(params, layout, m)
    return _pmlp.extract_member(params, layout, m)


@partial(jax.jit, static_argnames=("pop", "task", "fw"))
def _eval_batch(params, xb, tb, pop, task, fw):
    """One jitted eval batch under the training sharding (cached across
    ``evaluate_population`` calls on the jit cache — layouts are static
    hashable dataclasses, exactly like ``deep.sgd_step``)."""
    from repro.distributed.sharding import POP_LOGITS, POP_MEMBER, constrain
    logits = constrain(_forward(params, xb, pop, **dict(fw)),
                       POP_LOGITS)
    loss = constrain(member_losses(logits, tb, task), POP_MEMBER)
    acc = (constrain(member_accuracy(logits, tb), POP_MEMBER)
           if task == "classification" else jnp.zeros_like(loss))
    return loss, acc


def evaluate_population(params, pop, x, targets,
                        task: str = "classification", batch_size: int = 4096,
                        **fw):
    """Per-member metric over a full eval split (batched to bound memory).

    Runs under the TRAINING sharding: the jitted eval step consumes the
    sharded parameter tree as-is and constrains logits / per-member
    reductions to the population axis (no-op off-mesh), so selection over a
    mesh-sharded population never gathers the fused tensors to one device.

    Forward kwargs pass straight through to ``deep.forward`` — in
    particular ``infer=True`` (with ``bd_impl="fused"``) runs the whole
    eval on the forward-only serving kernels (DESIGN.md §10): no residual
    buffers, depth+1 launches per batch, identical metrics to f32
    tolerance.  That is how the serving engine scores members for its
    published set without ever touching the training kernels.

    Returns (losses (P,), accuracies (P,) or None)."""
    fw_key = tuple(sorted(fw.items()))      # hashable jit-static key
    n = x.shape[0]
    loss_sum = jnp.zeros(pop.num_members)
    acc_sum = jnp.zeros(pop.num_members)
    seen = 0
    for i in range(0, n, batch_size):
        xb, tb = x[i:i + batch_size], targets[i:i + batch_size]
        loss, acc = _eval_batch(params, xb, tb, pop, task, fw_key)
        loss_sum = loss_sum + loss * xb.shape[0]
        acc_sum = acc_sum + acc * xb.shape[0]
        seen += xb.shape[0]
    losses = loss_sum / seen
    accs = acc_sum / seen if task == "classification" else None
    return losses, accs


def _num_real(pop) -> int:
    """Members eligible for selection (shard-pad fillers are excluded)."""
    return getattr(pop, "num_real", pop.num_members)


def select_best(params, pop, losses) -> tuple[int, dict]:
    """Best member by eval loss → (index, standalone params).  Shard-pad
    filler members (trailing, ``LayeredPopulation.n_pad``) never win."""
    m = int(jnp.argmin(losses[:_num_real(pop)]))
    return m, extract_member(params, pop, m)


def _member_arch(pop, m: int):
    if isinstance(pop, LayeredPopulation):
        return pop.widths[m], "/".join(dict.fromkeys(pop.activations[m]))
    return pop.hidden_sizes[m], pop.activations[m]


def _check_member_ids(member_ids, nr: int):
    """Validate a survivor→original id mapping: one entry per real member
    and NO duplicates — a member born at rung r must never alias a pruned
    seed's id (the refill driver issues fresh ids from a monotone counter;
    a duplicate here means that invariant broke upstream)."""
    import numpy as np
    if len(member_ids) != nr:
        raise ValueError(f"member_ids has {len(member_ids)} entries for "
                         f"{nr} real members")
    ids = np.asarray(member_ids)
    if len(np.unique(ids)) != len(ids):
        dup = sorted(int(i) for i in ids[
            np.isin(ids, ids[np.concatenate(
                ([False], np.diff(np.sort(ids)) == 0))])])
        raise ValueError(f"member_ids contains duplicate original ids "
                         f"{sorted(set(dup))} — a refilled member is "
                         "aliasing a pruned member's id")


def _lineage_entry(lineage, member_id: int):
    """``lineage``: optional {original id → (parent id, birth rung)} from
    the refill controller; seeds (absent keys) report parent -1, rung 0."""
    if lineage is None:
        return None
    parent, born = lineage.get(int(member_id), (-1, 0))
    return {"member": int(member_id), "parent": int(parent),
            "born_rung": int(born)}


def leaderboard(pop, losses, accs=None, k: int = 10, member_ids=None,
                sort_by: str = "loss", lineage=None):
    """Top-k members as (rank, member, hidden, activation, loss[, acc]).

    For layered populations ``hidden`` is the member's width tuple;
    shard-pad filler members are excluded from the ranking.
    ``sort_by="acc"`` ranks by accuracy (descending) instead of loss —
    the serving engine publishes its member set off whichever metric the
    deployment optimises for.

    ``member_ids``: optional survivor→ORIGINAL id mapping (one entry per
    real member) from the successive-halving lifecycle — after compaction
    the fused layout renumbers members densely, but selection must keep
    speaking in the ids the run STARTED with, so ``member`` reports
    ``member_ids[m]`` and the layout slot moves to ``slot``.  The mapping
    must be duplicate-free (refilled members get FRESH ids, never a pruned
    seed's).

    ``lineage``: optional {original id → (parent id, birth rung)} from the
    slot-refill controller; when given, every row gains a ``lineage``
    column ({member, parent, born_rung}; seeds report parent -1, rung 0)
    so refilled members are distinguishable from seeds."""
    import numpy as np
    if member_ids is not None:
        _check_member_ids(member_ids, _num_real(pop))
    if sort_by == "loss":
        key = np.asarray(losses)[:_num_real(pop)]
    elif sort_by == "acc":
        if accs is None:
            raise ValueError("sort_by='acc' needs accuracies")
        key = -np.asarray(accs)[:_num_real(pop)]
    else:
        raise ValueError(f"unknown sort_by {sort_by!r} (have loss, acc)")
    order = np.argsort(key, kind="stable")[:k]
    rows = []
    for r, m in enumerate(order):
        hidden, act = _member_arch(pop, int(m))
        mid = int(m) if member_ids is None else int(member_ids[int(m)])
        row = dict(rank=r + 1, member=mid,
                   slot=int(m), hidden=hidden,
                   activation=act, loss=float(losses[m]))
        if accs is not None:
            row["acc"] = float(accs[m])
        lin = _lineage_entry(lineage, mid)
        if lin is not None:
            row["lineage"] = lin
        rows.append(row)
    return rows


def member_metrics(pop, losses, accs=None, member_ids=None, lineage=None):
    """Structured per-member metric rows for EVERY real member, unranked —
    the first slice of the metrics module (ROADMAP direction 3).  Each row
    is ``{member, slot, hidden, activation, depth, loss[, acc][, lineage]}``;
    the leaderboard is a sorted top-k view of exactly this table (same
    ``member_ids`` duplicate check, same ``lineage`` column).  Shard-pad
    fillers are excluded (their arrays hold identities, not models)."""
    import numpy as np
    nr = _num_real(pop)
    if member_ids is not None:
        _check_member_ids(member_ids, nr)
    rows = []
    for m in range(nr):
        hidden, act = _member_arch(pop, m)
        mid = m if member_ids is None else int(member_ids[m])
        row = dict(member=mid,
                   slot=m, hidden=hidden, activation=act,
                   depth=len(hidden) if isinstance(hidden, tuple) else 1,
                   loss=float(np.asarray(losses)[m]))
        if accs is not None:
            row["acc"] = float(np.asarray(accs)[m])
        lin = _lineage_entry(lineage, mid)
        if lin is not None:
            row["lineage"] = lin
        rows.append(row)
    return rows
