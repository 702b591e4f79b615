"""A configuration's members, and their weights made from the seed.

A configuration names its members by architecture (widths and activation).
Every weight of canonical member ``i`` (the configuration's own order) is a
counter-based hash of (seed, i, projection, row, column), so the same seed
gives the same member the same weights whatever layout holds it: the
program's fused layout (``ProgramLayout.pack``) and the reference's
blocks (``block_weights``) read the same numbers.  Projection ``j`` of a member of
depth ``d`` maps its layer ``j`` to layer ``j + 1`` (layer 0 the features,
layer ``d + 1`` the classes); its weight is ``(out, in)`` and both weight
and bias are uniform in +-1/sqrt(fan_in), as torch.nn.Linear draws them.

``ProgramLayout`` is the one place that knows the program's parameter
tree: it packs the weights into it and reduces a tree of that shape to
per-member sums of squares, per leaf.  It reads only public accessors of
the program's ``LayeredPopulation`` (widths, activations, ``layer_pop``,
``layer_width``, ``proj_buckets``) and the tree's leaf names; a program
whose layout or tree changes must keep those, or bring the benchmark a
way to pack per-member weights and read per-member sums of its own.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

MAX_WIDTH = 4096          # rows and columns addressed by the hash counter
PROJ_SLOTS = 64           # projection / bias slots per member


def expand(cfg: dict) -> list:
    """The configuration's members as ``(widths, activation)`` pairs."""
    pop = cfg["population"]
    acts = pop["activations"]
    if pop["kind"] == "grid":
        # ``grids`` copies of the grid, each with ``repeats`` of every cell
        lo, hi = pop["hidden"]
        n = pop["repeats"] * pop.get("grids", 1)
        out = [((h,), a) for a in acts for h in range(lo, hi + 1)
               for _ in range(n)]
    elif pop["kind"] == "list":
        # member i: widths i % W, activation (i // W) % A, so that every
        # width meets every activation
        widths = [tuple(w) for w in pop["widths"]]
        n = len(widths) * pop["repeats"]
        out = [(widths[i % len(widths)],
                acts[(i // len(widths)) % len(acts)]) for i in range(n)]
    else:
        raise ValueError(f"unknown population kind {pop['kind']!r}")
    for w, _ in out:
        if max(w) >= MAX_WIDTH or 2 * len(w) + 2 > PROJ_SLOTS:
            raise ValueError(f"member {w} is outside the weight counter")
    return out


def seed_words(seed: int) -> np.ndarray:
    """Any whole seed -> two uint32 words of hash key."""
    return np.random.SeedSequence(int(seed)).generate_state(2)


def _fmix(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def uniform(key, c0, c1):
    """Elementwise uniform in [-1, 1) from uint32 counters ``c0``, ``c1``
    (broadcast against each other) under the two-word ``key``."""
    c0 = jnp.asarray(c0).astype(jnp.uint32)
    c1 = jnp.asarray(c1).astype(jnp.uint32)
    h = _fmix(c0 * jnp.uint32(0x9E3779B1) ^ key[0])
    h = _fmix(h ^ (c1 * jnp.uint32(0x85EBCA77)) ^ key[1])
    h = _fmix(h + c1)
    return (h >> 8).astype(jnp.float32) * (2.0 ** -23) - 1.0


def slot(member, proj, bias):
    """Counter ``c0`` of a member's projection weight or bias."""
    return member * PROJ_SLOTS + 2 * proj + bias


# ---------------------------------------------------------------------- #
# blocks: the reference's layout                                         #
# ---------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class Block:
    """Up to ``max_n`` members of one depth and activation, padded to a
    multiple of 8 members and to the widest member on every layer."""
    act: str
    widths: tuple            # per layer: features, hidden..., classes
    idx: np.ndarray          # canonical member index per slot (-1: empty)

    @property
    def depth(self) -> int:
        return len(self.widths) - 2

    @property
    def n(self) -> int:
        return len(self.idx)


def blocks(members: list, n_features: int, n_classes: int,
           max_n: int = 1024) -> list:
    groups = {}
    for i, (w, a) in enumerate(members):
        groups.setdefault((len(w), a), []).append(i)
    out = []
    for (d, a), idx in sorted(groups.items()):
        for s in range(0, len(idx), max_n):
            part = idx[s: s + max_n]
            wmax = tuple(max(members[i][0][l] for i in part)
                         for l in range(d))
            n = -(-len(part) // 8) * 8
            out.append(Block(a, (n_features,) + wmax + (n_classes,),
                             np.array(part + [-1] * (n - len(part)))))
    return out


def block_arrays(members: list, blk: Block):
    """Host inputs of ``block_weights``: the canonical index of every slot
    and, per layer, ``(n, width)`` masks of each member's real units."""
    real = [np.zeros((blk.n, blk.widths[l]), np.float32)
            for l in range(blk.depth + 2)]
    for t, i in enumerate(blk.idx):
        if i < 0:
            continue
        dims = (blk.widths[0],) + members[i][0] + (blk.widths[-1],)
        for l, h in enumerate(dims):
            real[l][t, :h] = 1.0
    return np.maximum(blk.idx, 0).astype(np.int32), real


@jax.jit
def block_weights(key, m, real):
    """Weights, biases and hidden-unit masks of one block, all ``(n, ...)``:
    ``ws[j]`` is ``(n, out, in)``, ``bs[j]`` is ``(n, out)``.  Units that
    ``real`` (from ``block_arrays``) marks empty are zero."""
    ws, bs = [], []
    for j in range(len(real) - 1):
        fan = jnp.maximum(real[j].sum(1), 1.0)
        bound = 1.0 / jnp.sqrt(fan)
        rows = jnp.arange(real[j + 1].shape[1])
        cols = jnp.arange(real[j].shape[1])
        c1 = rows[:, None] * MAX_WIDTH + cols[None, :]
        w = uniform(key, slot(m, j, 0)[:, None, None], c1[None])
        wmask = real[j + 1][:, :, None] * real[j][:, None, :]
        ws.append(w * bound[:, None, None] * wmask)
        b = uniform(key, slot(m, j, 1)[:, None], (rows * MAX_WIDTH)[None, :])
        bs.append(b * bound[:, None] * real[j + 1])
    return ws, bs, real[1:-1]


# ---------------------------------------------------------------------- #
# the program's layout                                                   #
# ---------------------------------------------------------------------- #

class ProgramLayout:
    """Maps canonical members onto the program's fused layout ``lp`` (a
    ``LayeredPopulation`` in the program's member order)."""

    def __init__(self, lp, members: list):
        self.lp = lp
        by_arch = {}
        for i, (w, a) in enumerate(members):
            by_arch.setdefault((w, (a,) * len(w)), []).append(i)
        canon = []
        for m in range(lp.num_real):
            key = (lp.widths[m], lp.activations[m])
            if not by_arch.get(key):
                raise ValueError(f"program member {m} {key} is not in the "
                                 "configuration")
            canon.append(by_arch[key].pop(0))
        if any(by_arch.values()):
            raise ValueError("configuration members missing from the "
                             "program's layout")
        # program member -> canonical index (shard-pad fillers: -1)
        self.canon = np.array(canon + [-1] * lp.n_pad, np.int64)
        self.depths = np.array([len(w) for w in lp.widths])

    def _rows(self, l: int):
        """Per fused unit of hidden layer ``l``: program member, unit within
        the member, and whether the unit is real in a real layer."""
        pop = self.lp.layer_pop(l)
        seg = np.asarray(pop.segment_ids).astype(np.int64)
        unit = np.arange(pop.total_hidden) - np.asarray(pop.offsets)[seg]
        sizes = np.asarray(pop.hidden_sizes)[seg]
        ok = (unit < sizes) & (self.canon[seg] >= 0)
        return seg, unit, ok

    def index_arrays(self) -> dict:
        """Host arrays that ``pack`` reads (jit arguments, not constants)."""
        lp, canon = self.lp, self.canon
        seg0, u0, ok0 = self._rows(0)
        out = {"in_m": canon[seg0], "in_u": u0, "in_ok": ok0}
        for l in range(lp.depth - 1):
            seg, u, ok = self._rows(l + 1)
            real_layer = self.depths[seg] > l + 1
            prev = np.array([lp.layer_width(m, l) for m in seg])
            out[f"mid{l}_b"] = (canon[seg], u, ok & real_layer, prev)
        segL, uL, okL = self._rows(lp.depth - 1)
        out["out_m"], out["out_u"], out["out_ok"] = canon[segL], uL, okL
        out["out_d"] = self.depths[segL]
        out["last_w"] = np.array([lp.widths[m][-1] for m in segL])
        out["bout_m"], out["bout_d"] = canon, self.depths
        out["bout_w"] = np.array([w[-1] for w in lp.widths])
        return out

    def segments(self) -> list:
        """Member of every fused unit, per hidden layer (host arrays that
        ``member_sumsq`` reads)."""
        return [np.asarray(self.lp.layer_pop(l).segment_ids)
                for l in range(self.lp.depth)]

    def pack(self, key, ix: dict) -> dict:
        """The program's parameter tree for these members (traceable)."""
        lp = self.lp
        f = lp.in_features
        cols = jnp.arange(f)
        ok = ix["in_ok"].astype(jnp.float32)
        m = jnp.maximum(ix["in_m"], 0)
        bound = 1.0 / np.sqrt(f)
        w_in = uniform(key, slot(m, 0, 0)[:, None],
                       ix["in_u"][:, None] * MAX_WIDTH + cols[None, :])
        params = {
            "w_in": w_in * bound * ok[:, None],
            "b_in": uniform(key, slot(m, 0, 1), ix["in_u"] * MAX_WIDTH)
            * bound * ok,
            "mid": []}
        for l in range(lp.depth - 1):
            ws = []
            for (m0, n, hin, hout, _oi, _oo, real) in lp.proj_buckets(l):
                if not real:
                    continue
                mem = self.canon[m0: m0 + n]
                w_in_real = np.array([lp.layer_width(p, l)
                                      for p in range(m0, m0 + n)])
                w_out_real = np.array([lp.layer_width(p, l + 1)
                                       for p in range(m0, m0 + n)])
                rows, cc = np.arange(hout), np.arange(hin)
                mask = ((rows[None, :, None] < w_out_real[:, None, None])
                        & (cc[None, None, :] < w_in_real[:, None, None])
                        & (mem[:, None, None] >= 0))
                w = uniform(key, slot(np.maximum(mem, 0), l + 1, 0)
                            [:, None, None],
                            (rows[:, None] * MAX_WIDTH + cc[None, :])[None])
                ws.append(w * (1.0 / np.sqrt(w_in_real))[:, None, None]
                          * mask)
            bm, bu, bok, prev = ix[f"mid{l}_b"]
            b = uniform(key, slot(jnp.maximum(bm, 0), l + 1, 1),
                        bu * MAX_WIDTH)
            params["mid"].append({
                "w": ws,
                "b": b / jnp.sqrt(prev.astype(jnp.float32))
                * bok.astype(jnp.float32)})
        om = jnp.maximum(ix["out_m"], 0)
        o = jnp.arange(lp.out_features)
        w_out = uniform(key, slot(om, ix["out_d"], 0)[None, :],
                        o[:, None] * MAX_WIDTH + ix["out_u"][None, :])
        params["w_out"] = (w_out / jnp.sqrt(ix["last_w"].astype(jnp.float32))
                           [None, :] * ix["out_ok"].astype(jnp.float32)
                           [None, :])
        bm = jnp.maximum(ix["bout_m"], 0)
        b_out = uniform(key, slot(bm, ix["bout_d"], 1)[:, None],
                        (o * MAX_WIDTH)[None, :])
        params["b_out"] = (b_out / jnp.sqrt(ix["bout_w"].astype(jnp.float32))
                           [:, None] * (ix["bout_m"] >= 0)[:, None])
        return params

    def member_sumsq(self, tree, segs) -> dict:
        """Per program member, per leaf, the sum of squares of a tree shaped
        like the parameters (traceable; ``segs`` as ``segments`` gives
        them): ``{leaf: (P,)}``."""
        lp = self.lp
        P = lp.num_members

        def seg_sum(v, l):
            return jax.ops.segment_sum(v, segs[l], num_segments=P)

        out = {"w_in": seg_sum(jnp.sum(tree["w_in"] ** 2, axis=1), 0),
               "b_in": seg_sum(tree["b_in"] ** 2, 0)}
        for l in range(lp.depth - 1):
            acc = jnp.zeros((P,), jnp.float32)
            wi = 0
            for (m0, n, *_r, real) in lp.proj_buckets(l):
                if real:
                    w = tree["mid"][l]["w"][wi]
                    acc = acc.at[m0: m0 + n].add(jnp.sum(w ** 2, axis=(1, 2)))
                    wi += 1
            out[f"mid{l}.w"] = acc
            out[f"mid{l}.b"] = seg_sum(tree["mid"][l]["b"] ** 2, l + 1)
        out["w_out"] = seg_sum(jnp.sum(tree["w_out"] ** 2, axis=0),
                               lp.depth - 1)
        out["b_out"] = jnp.sum(tree["b_out"] ** 2, axis=1)
        return out
