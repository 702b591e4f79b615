"""Plain float32 reference of population training, and the numbers that
compare the program with it.

Members are independent MLPs, so the reference trains them in blocks
(``members.Block``: one depth and activation, widths padded with masked
units) with ordinary per-member einsums at ``Precision.HIGHEST``, softmax
cross-entropy averaged over the batch, and the configuration's optimizer
written out: SGD ``p -= lr * g``, or AdamW with bias correction and
decoupled weight decay.  It imports nothing of the program.

``precision="bf16x2"`` is the control: every matmul operand, forward and
backward, rounded to 16 significant bits, the precision of the pair of
bfloat16 values that a three-pass bfloat16 product (``high``) holds.
``precision="split"`` is the same float32 arithmetic summed in another
order, a reference as sound as the first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import members as mb

EXCLUDE_BELOW = 1e-3      # a leaf whose first gradient is under this share
                          # of the median leaf's moves by round-off alone


def _acts():
    return {
        "identity": lambda x: x,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "relu": jax.nn.relu,
        "elu": jax.nn.elu,
        "selu": jax.nn.selu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=False),
        "leaky_relu": lambda x: jax.nn.leaky_relu(x, 0.01),
        "hardshrink": lambda x: jnp.where(jnp.abs(x) > 0.5, x, 0.0),
        "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x)),
    }


ACT_NAMES = ("identity", "sigmoid", "tanh", "relu", "elu", "selu", "gelu",
             "leaky_relu", "hardshrink", "mish")


def _round16(x):
    """``x`` rounded to 16 significant bits, in integer arithmetic (a
    float round trip through bfloat16 may be elided by the compiler)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32(0x80)) & jnp.uint32(0xFFFFFF00)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


@functools.cache
def _ct_round():
    """Identity whose cotangent is rounded to 16 bits on the way back."""
    @jax.custom_vjp
    def f(x):
        return x

    f.defvjp(lambda x: (x, None), lambda _, g: (_round16(g),))
    return f


def _mm(spec, a, b, precision):
    hp = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=hp)
    if precision == "split":
        # the same float32 product summed in another order: the two halves
        # of the contracted (last) axis apart, then added
        h = a.shape[-1] // 2
        return (jnp.einsum(spec, a[..., :h], b[..., :h], precision=hp)
                + jnp.einsum(spec, a[..., h:], b[..., h:], precision=hp))
    sg = jax.lax.stop_gradient
    a = a + sg(_round16(a) - a)
    b = b + sg(_round16(b) - b)
    return _ct_round()(jnp.einsum(spec, a, b, precision=hp))


def _member_loss(ws, bs, masks, act_id, x, y, precision):
    """Per-member mean cross-entropy over the batch, ``(n,)``."""
    acts = _acts()
    branches = [acts[a] for a in ACT_NAMES]
    h = None
    for j, (w, b) in enumerate(zip(ws, bs)):
        if j == 0:
            z = _mm("bf,nhf->bnh", x, w, precision)
        else:
            z = _mm("bnh,noh->bno", h, w, precision)
        z = z + b[None]
        if j == len(ws) - 1:
            logp = jax.nn.log_softmax(z, axis=-1)
            nll = -jnp.take_along_axis(logp, y[:, None, None], axis=-1)[..., 0]
            return nll.mean(axis=0)
        h = jax.lax.switch(act_id, branches, z) * masks[j][None]


@functools.partial(jax.jit,
                   static_argnames=("opt", "precision"))
def train_block(ws, bs, masks, valid, act_id, xs, ys, lr, opt, precision):
    """``len(xs)`` optimizer steps of one block.  Returns the per-step
    per-member losses and, per leaf, the per-member sums of squares of the
    first gradient, of the first moment after the last step (AdamW), and
    of the change of the parameters."""
    name, hyper = opt[0], dict(opt[1:])

    def total(params, x, y):
        per = _member_loss(params[0], params[1], masks, act_id, x, y,
                           precision)
        return jnp.sum(per * valid), per

    params0 = (list(ws), list(bs))
    zeros = jax.tree.map(jnp.zeros_like, params0)

    def step(carry, batch):
        p, m, v, k = carry
        (_, per), g = jax.value_and_grad(total, has_aux=True)(p, *batch)
        k = k + 1
        if name == "sgd":
            p = jax.tree.map(lambda a, b: a - lr * b, p, g)
        else:
            b1, b2, eps = hyper["b1"], hyper["b2"], hyper["eps"]
            wd = hyper.get("weight_decay", 0.0)
            m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
            v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
            bc1 = 1.0 - b1 ** k.astype(jnp.float32)
            bc2 = 1.0 - b2 ** k.astype(jnp.float32)
            p = jax.tree.map(
                lambda a, mm, vv: a - lr * ((mm / bc1) / (jnp.sqrt(vv / bc2)
                                                        + eps) + wd * a),
                p, m, v)
        return (p, m, v, k), (per, g)

    (p, m, v, _), (pers, gs) = jax.lax.scan(
        step, (params0, zeros, zeros, jnp.zeros((), jnp.int32)), (xs, ys))
    g1 = jax.tree.map(lambda a: a[0], gs)
    change = jax.tree.map(lambda a, b: a - b, p, params0)

    def sumsq(tree):
        return [jnp.sum(a.reshape(a.shape[0], -1) ** 2, axis=1)
                for a in tree[0] + tree[1]]

    return {"pers": pers, "grad1": sumsq(g1), "change": sumsq(change),
            "moment": sumsq(m)}


def opt_key(cfg: dict) -> tuple:
    """The configuration's optimizer as a hashable ``(name, (k, v)...)``."""
    o = dict(cfg["optimizer"])
    return (o.pop("name"),) + tuple(sorted((k, v) for k, v in o.items()
                                           if k != "lr"))


def run(cfg: dict, members: list, key, xs, ys, *, precision="highest",
        device=None) -> dict:
    """Train every member for ``len(xs)`` steps on ``(xs, ys)`` → per
    canonical member: ``pers (S, N)`` and ``{leaf: (N,)}`` sums of squares
    under ``grad1``, ``change`` and ``moment``."""
    n_all = len(members)
    S = len(xs)
    out = {"pers": np.zeros((S, n_all))}
    for kind in ("grad1", "change", "moment"):
        out[kind] = {}
    opt = opt_key(cfg)
    put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
    xs, ys = put(np.asarray(xs)), put(np.asarray(ys))
    lr = float(cfg["optimizer"]["lr"])
    for blk in mb.blocks(members, cfg["in_features"], cfg["classes"]):
        m, real = mb.block_arrays(members, blk)
        ws, bs, masks = mb.block_weights(put(key), put(m),
                                         [put(r) for r in real])
        valid = put((blk.idx >= 0).astype(np.float32))
        r = jax.device_get(train_block(
            ws, bs, masks, valid, put(np.int32(ACT_NAMES.index(blk.act))),
            xs, ys, lr, opt, precision))
        sel = blk.idx >= 0
        idx = blk.idx[sel]
        out["pers"][:, idx] = np.asarray(r["pers"], np.float64)[:, sel]
        mids = range(blk.depth - 1)
        order = (["w_in"] + [f"mid{l}.w" for l in mids] + ["w_out"]
                 + ["b_in"] + [f"mid{l}.b" for l in mids] + ["b_out"])
        for kind in ("grad1", "change", "moment"):
            for leaf, v in zip(order, r[kind]):
                arr = out[kind].setdefault(leaf, np.zeros(n_all))
                arr[idx] = np.asarray(v, np.float64)[sel]
    return out


# ---------------------------------------------------------------------- #
# the numbers compared                                                   #
# ---------------------------------------------------------------------- #

def _leaf_norms(sums: dict, chip_of: np.ndarray, n_chips: int) -> dict:
    out = {}
    for leaf, v in sums.items():
        per_chip = np.bincount(chip_of, weights=np.asarray(v, np.float64),
                               minlength=n_chips)
        for c in range(n_chips):
            out[(leaf, c)] = float(np.sqrt(per_chip[c]))
    return out


def _leaf_gaps(prog: dict, ref: dict, keep: list) -> dict:
    scale = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog.get(k, 0.0) - ref[k]) / max(ref[k], scale)
            for k in keep}


def _member_gaps(prog: dict, ref: dict, keep: list,
                 chip_of: np.ndarray) -> np.ndarray:
    """Per member, the widest gap over its leaves between the program's and
    the reference's norms of that member's part of the leaf, over the
    larger of the reference's norm and the median member's of that leaf.
    Only the (leaf, chip) pairs in ``keep`` count."""
    worst = np.zeros(len(chip_of))
    for leaf, r in ref.items():
        r = np.sqrt(np.asarray(r, np.float64))
        p = np.sqrt(np.asarray(prog[leaf], np.float64))
        has = (r > 0) & np.isin(chip_of, [c for l, c in keep if l == leaf])
        if has.any():
            gap = np.abs(p - r) / np.maximum(r, np.median(r[has]))
            worst = np.maximum(worst, np.where(has, gap, 0.0))
    return worst


def _tenth(v: np.ndarray) -> float:
    """The 10th largest value (one member, or a few, may step across a
    jump of its activation's derivative on a rounding difference)."""
    return float(np.sort(v)[::-1][min(9, len(v) - 1)])


def numbers(prog: dict, ref: dict, chip_of: np.ndarray, n_chips: int,
            adam: bool) -> dict:
    """The compared numbers of one run.

    ``prog`` holds the program's readings in canonical member order:
    ``losses (S,)`` (its total loss per step), ``pers (S, N)``, and
    ``{leaf: (N,)}`` sums of squares under ``change`` and, with AdamW,
    ``moment``.

    ``loss``: the widest relative gap of the total loss over the steps;
    ``first_loss``: that gap at the first step.
    ``member_loss_10th``: each member's widest gap of its own loss over the
    steps, the tenth largest over the members (a member with a
    discontinuous activation, hardshrink, can step across it on a rounding
    difference, and one such member must not decide the run).
    ``change`` / ``moment``: the widest gap between the program's and the
    reference's norms of a leaf's change (of AdamW's first moment), over
    the larger of that leaf's reference norm and the median leaf's;
    ``change_median_leaf`` / ``moment_median_leaf``: the median over the
    leaves of that gap.  ``change_member_10th`` / ``moment_member_10th``:
    each member's widest gap over its own leaves (``_member_gaps``), the
    10th largest over the members.  A leaf is a parameter's part on one
    chip; leaves whose first reference gradient is under ``EXCLUDE_BELOW``
    of the median leaf's are left out.

    Which of these a cell compares is the set of keys of its limits.  Under
    AdamW the first step moves every weight by about the learning rate
    whatever its gradient's size, so one member whose activation's
    derivative jumps (leaky_relu, selu at 0) on a rounding difference can
    move the total loss after the first update, every leaf it has a part
    in, and with them the median leaf: AdamW cells compare the first step's
    loss and the members' 10th largest gaps.
    """
    ref_tot = ref["pers"].sum(axis=1)
    rel = (np.abs(np.asarray(prog["losses"], np.float64) - ref_tot)
           / np.abs(ref_tot))
    out = {"loss": float(rel.max()), "first_loss": float(rel[0])}
    gap = np.abs(np.asarray(prog["pers"], np.float64) - ref["pers"]).max(0)
    out["member_loss_10th"] = _tenth(gap)
    out["_worst_member"] = int(np.argmax(gap))
    g1 = _leaf_norms(ref["grad1"], chip_of, n_chips)
    live = [k for k, v in g1.items() if v > 0]
    med = float(np.median([g1[k] for k in live]))
    keep = [k for k in live if g1[k] >= EXCLUDE_BELOW * med]
    kinds = ("change", "moment") if adam else ("change",)
    for kind in kinds:
        gaps = _leaf_gaps(_leaf_norms(prog[kind], chip_of, n_chips),
                          _leaf_norms(ref[kind], chip_of, n_chips), keep)
        worst = max(gaps, key=gaps.get)
        out[kind] = gaps[worst]
        out[f"{kind}_median_leaf"] = float(np.median(list(gaps.values())))
        out[f"_{kind}_leaf"] = f"{worst[0]}@{worst[1]}"
        out[f"{kind}_member_10th"] = _tenth(_member_gaps(
            prog[kind], ref[kind], keep, chip_of))
    out["leaves_left_out"] = len(live) - len(keep)
    return out
