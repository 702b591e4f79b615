"""Population descriptor: a heterogeneous collection of independent MLPs fused
into a single tensor layout (the paper's ParallelMLPs).

A population of P members, member ``m`` having ``hidden_sizes[m]`` hidden units
and activation ``activations[m]``, is laid out as one fused hidden axis of
``total_hidden`` units.  Every member's slice is padded up to a multiple of
``block`` so that, on TPU, each 128-lane tile belongs to exactly one member —
this is what turns the paper's scatter-add into a segment-blocked matmul
(DESIGN.md §2).  Padded units are masked to zero after activation, so they
receive zero gradient and the fused network is mathematically identical to the
P independent networks.

``Population`` is the PER-LAYER layout primitive: it owns the bucketing logic
(``size_buckets`` for the M3 output projection, ``pair_buckets`` for
block-diagonal layer→layer projections).  ``LayeredPopulation`` composes one
``Population`` per hidden layer into a deep population with HETEROGENEOUS
member depths (shallow members ride through later layers as exact identity
pass-throughs) and per-layer activations (DESIGN.md §3).

All layout quantities are static Python data (computed at trace time), so jit
sees them as compile-time constants; only the parameter/activation tensors are
traced.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import cached_property
from typing import Sequence

import numpy as np

from repro.core.activations import ACTIVATION_NAMES


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _instance_cache(method):
    """Memoise a method on the instance (``__dict__``, like cached_property —
    works on frozen dataclasses and dies with the instance; a process-global
    lru_cache would pin every layout ever constructed)."""
    name = method.__name__

    @functools.wraps(method)
    def wrapper(self, *args):
        cache = self.__dict__.setdefault("_method_cache", {})
        key = (name, args)
        if key not in cache:
            cache[key] = method(self, *args)
        return cache[key]
    return wrapper


@dataclasses.dataclass(frozen=True)
class Population:
    """Static description of a fused population of independent MLPs.

    Members are stored in the order given; callers that want efficient sliced
    activation application should construct with ``sort_members=True`` (groups
    members by activation so each activation is applied to one contiguous
    slice).
    """

    in_features: int
    out_features: int
    hidden_sizes: tuple
    activations: tuple  # activation *names*, one per member
    block: int = 1      # hidden-slice alignment (128 for TPU kernels)

    def __post_init__(self):
        if len(self.hidden_sizes) != len(self.activations):
            raise ValueError(
                f"hidden_sizes ({len(self.hidden_sizes)}) and activations "
                f"({len(self.activations)}) must have the same length")
        for a in self.activations:
            if a not in ACTIVATION_NAMES:
                raise ValueError(f"unknown activation {a!r}; "
                                 f"known: {sorted(ACTIVATION_NAMES)}")
        for h in self.hidden_sizes:
            if h < 1:
                raise ValueError(f"hidden size must be >= 1, got {h}")
        if self.block < 1:
            raise ValueError("block must be >= 1")
        # normalise to tuples (allows list inputs)
        object.__setattr__(self, "hidden_sizes", tuple(int(h) for h in self.hidden_sizes))
        object.__setattr__(self, "activations", tuple(self.activations))

    # ------------------------------------------------------------------ #
    # constructors                                                       #
    # ------------------------------------------------------------------ #
    @staticmethod
    def grid(in_features: int, out_features: int,
             hidden_range: Sequence[int], activations: Sequence[str],
             repeats: int = 1, block: int = 1,
             sort_members: bool = True, sort_by: str = "act") -> "Population":
        """The paper's experimental design: every (hidden size × activation)
        pair, repeated ``repeats`` times.  hidden 1..100 × 10 activations ×
        10 repeats = the paper's 10,000 models."""
        sizes, acts = [], []
        for a in activations:
            for h in hidden_range:
                for _ in range(repeats):
                    sizes.append(h)
                    acts.append(a)
        pop = Population(in_features, out_features, tuple(sizes), tuple(acts),
                         block=block)
        return pop.sorted(sort_by) if sort_members else pop

    def sorted(self, by: str = "act") -> "Population":
        """Reorder members so fused ops touch contiguous slices.

        by="act"  — (activation, size): one activation run per function
                    (best when activation dispatch dominates; default).
        by="size" — (padded size, activation): one M3 bucket per size CLASS,
                    merging buckets across activations — at block=8 the
                    paper grid collapses 130 bucket einsums to 13 while
                    keeping tight padding (§Perf hillclimb, paper cell)."""
        if by == "act":
            key = lambda m: (self.activations[m], self.hidden_sizes[m])
        elif by == "size":
            key = lambda m: (_round_up(self.hidden_sizes[m], self.block),
                             self.activations[m], self.hidden_sizes[m])
        else:
            raise ValueError(by)
        order = sorted(range(self.num_members), key=key)
        return dataclasses.replace(
            self,
            hidden_sizes=tuple(self.hidden_sizes[m] for m in order),
            activations=tuple(self.activations[m] for m in order),
        )

    # ------------------------------------------------------------------ #
    # layout (all static numpy, computed once)                           #
    # ------------------------------------------------------------------ #
    @property
    def num_members(self) -> int:
        return len(self.hidden_sizes)

    @cached_property
    def padded_sizes(self) -> np.ndarray:
        """Per-member hidden size rounded up to ``block``. shape (P,)."""
        return np.array([_round_up(h, self.block) for h in self.hidden_sizes],
                        dtype=np.int32)

    @cached_property
    def offsets(self) -> np.ndarray:
        """Start offset of member m's slice in the fused hidden axis. (P+1,)."""
        return np.concatenate([[0], np.cumsum(self.padded_sizes)]).astype(np.int32)

    @property
    def total_hidden(self) -> int:
        return int(self.offsets[-1])

    @cached_property
    def segment_ids(self) -> np.ndarray:
        """Member id for every fused hidden unit. shape (total_hidden,)."""
        return np.repeat(np.arange(self.num_members, dtype=np.int32),
                         self.padded_sizes)

    @cached_property
    def hidden_mask(self) -> np.ndarray:
        """1.0 for real hidden units, 0.0 for alignment padding. (total_hidden,)."""
        mask = np.zeros(self.total_hidden, dtype=np.float32)
        for m in range(self.num_members):
            mask[self.offsets[m]: self.offsets[m] + self.hidden_sizes[m]] = 1.0
        return mask

    @cached_property
    def act_ids(self) -> np.ndarray:
        """Activation id (index into ACTIVATION_NAMES order used by
        activations.apply_*) for every fused hidden unit. (total_hidden,)."""
        names = sorted(ACTIVATION_NAMES)
        lut = {n: i for i, n in enumerate(names)}
        per_member = np.array([lut[a] for a in self.activations], dtype=np.int32)
        return np.repeat(per_member, self.padded_sizes)

    @cached_property
    def act_runs(self):
        """Contiguous runs of identical activation: list of
        (act_name, start, stop) covering [0, total_hidden).  One run per
        activation if the population is sorted."""
        runs = []
        seg_acts = [self.activations[m] for m in range(self.num_members)]
        start = 0
        m = 0
        while m < self.num_members:
            a = seg_acts[m]
            stop_m = m
            while stop_m + 1 < self.num_members and seg_acts[stop_m + 1] == a:
                stop_m += 1
            stop = int(self.offsets[stop_m + 1])
            runs.append((a, start, stop))
            start = stop
            m = stop_m + 1
        return runs

    @cached_property
    def member_fan_in(self) -> np.ndarray:
        """Fan-in of the output layer per fused hidden unit (= its member's
        true hidden size); used for per-member init scaling. (total_hidden,)."""
        return np.repeat(np.array(self.hidden_sizes, dtype=np.float32),
                         self.padded_sizes)

    @cached_property
    def block_segment_ids(self) -> np.ndarray:
        """Member id per hidden *block* (total_hidden // block,).  Well defined
        because every member slice is block-aligned; this is the scalar-prefetch
        input of the Pallas segment-blocked matmul."""
        assert self.total_hidden % self.block == 0
        return self.segment_ids[:: self.block].copy()

    @cached_property
    def block_act_ids(self) -> np.ndarray:
        """Activation id per hidden block (scalar prefetch for the fused
        kernels' activation epilogue)."""
        assert self.total_hidden % self.block == 0
        return self.act_ids[:: self.block].copy()

    # ------------------------------------------------------------------ #
    # bucketing primitives (shared by M3 and the block-diagonal layers)  #
    # ------------------------------------------------------------------ #
    @_instance_cache
    def size_buckets(self):
        """Contiguous runs of members with identical *padded* size.

        The M3 bucketed implementation reshapes each run to (B, n, hs) and
        batched-matmuls it.  ``Population.grid`` sorts by (activation, size),
        so runs are short; the general case still works, just with more
        buckets.  Returns static (start_member, n_members, padded_size,
        start_col) tuples.
        """
        out = []
        sizes = self.padded_sizes
        m = 0
        while m < self.num_members:
            n = 1
            while m + n < self.num_members and sizes[m + n] == sizes[m]:
                n += 1
            out.append((m, n, int(sizes[m]), int(self.offsets[m])))
            m += n
        return tuple(out)

    def pair_buckets(self, out_pop: "Population", keys: Sequence = None):
        """Contiguous runs of members with identical padded (in, out) widths
        for a block-diagonal ``self``→``out_pop`` projection (member m's units
        in ``out_pop`` contract ONLY member m's units in ``self``).

        ``keys`` (optional, one hashable per member) further splits runs —
        LayeredPopulation uses it to separate real projections from identity
        pass-throughs.  Returns static (start_member, n_members, padded_in,
        padded_out, in_offset, out_offset) tuples.
        """
        if out_pop.num_members != self.num_members:
            raise ValueError("pair_buckets: member count mismatch "
                             f"({self.num_members} vs {out_pop.num_members})")
        runs = []
        m = 0
        while m < self.num_members:
            n = 1
            key = (self.padded_sizes[m], out_pop.padded_sizes[m],
                   None if keys is None else keys[m])
            while m + n < self.num_members and \
                    (self.padded_sizes[m + n], out_pop.padded_sizes[m + n],
                     None if keys is None else keys[m + n]) == key:
                n += 1
            runs.append((m, n, int(key[0]), int(key[1]),
                         int(self.offsets[m]), int(out_pop.offsets[m])))
            m += n
        return tuple(runs)

    def member_slice(self, m: int) -> slice:
        """Slice of member m's REAL units (excludes padding)."""
        return slice(int(self.offsets[m]), int(self.offsets[m]) + self.hidden_sizes[m])

    def param_specs(self):
        """PartitionSpec tree matching ``parallel_mlp.init_params`` — every
        member-major axis (fused hidden, member) shards over the population
        axis; feature/class axes replicate.  Axes that the ambient mesh lacks
        or that don't divide degrade to replication via ``filter_spec``."""
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import POP_AXIS
        return {"w1": P(POP_AXIS, None), "b1": P(POP_AXIS),
                "w2": P(None, POP_AXIS), "b2": P(POP_AXIS, None)}

    def describe(self) -> str:
        import collections
        by_act = collections.Counter(self.activations)
        return (f"Population(P={self.num_members}, total_hidden={self.total_hidden}, "
                f"block={self.block}, in={self.in_features}, out={self.out_features}, "
                f"acts={dict(by_act)})")

    def layered(self) -> "LayeredPopulation":
        """This population as a depth-1 LayeredPopulation (same layout)."""
        return LayeredPopulation(
            self.in_features, self.out_features,
            tuple((h,) for h in self.hidden_sizes),
            tuple((a,) for a in self.activations), block=self.block)


# ---------------------------------------------------------------------- #
# layered populations (heterogeneous depths, per-layer activations)      #
# ---------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class BlockDiagLayout:
    """Static scalar-prefetch metadata for one block-diagonal l→l+1
    projection run as a single Pallas segment-blocked matmul
    (kernels/fused_layer.py; DESIGN.md §3/§7).

    The fused weight is a flat array of (block × block) tiles, member-major,
    row-major over each member's (out_tile, in_tile) grid, with ONE shared
    identity tile appended at index ``n_param_blocks`` (used by pass-through
    members; it is not a parameter).

    The reduction is RAGGED (members have different fan-ins), so instead of
    a dense (out_tiles × k_max) grid — which wastes a clamped re-read on
    every tile whose fan-in is below the maximum — the kernel runs one grid
    step per REAL (output tile, reduction k) pair: step ``s`` reads input
    tile ``s_in[s]`` against weight tile ``s_w[s]`` and accumulates into
    output tile ``s_out[s]``; ``s_first/s_last`` flag the accumulator
    init/flush edges of each output tile's (consecutive) run.  ``n_steps``
    is exactly the number of MXU tiles of work — no dead grid points.
    The ``*_t`` fields describe the TRANSPOSED projection (used for dh in
    the custom VJP), and ``wb_out_tile/wb_in_tile`` map each parameter tile
    to its (dy, h) tile pair for the dw kernel.
    """
    block: int
    n_in_tiles: int
    n_out_tiles: int
    n_param_blocks: int
    n_steps: int
    s_in: tuple
    s_w: tuple
    s_out: tuple
    s_first: tuple
    s_last: tuple
    n_steps_t: int
    s_in_t: tuple
    s_w_t: tuple
    s_out_t: tuple
    s_first_t: tuple
    s_last_t: tuple
    s_q_t: tuple         # param tile touched at each transposed step (the
                         # dw target of the one-pass fused backward;
                         # n_param_blocks = the discarded dummy slot)
    perm_t: tuple        # WB_aug permutation building the transposed tiles
    wb_out_tile: tuple   # per parameter tile
    wb_in_tile: tuple


def _normalise_member_acts(acts, depth_m: int, member: int):
    if isinstance(acts, str):
        acts = (acts,) * depth_m
    acts = tuple(acts)
    if len(acts) != depth_m:
        raise ValueError(
            f"member {member}: {len(acts)} activations for depth {depth_m}")
    for a in acts:
        if a not in ACTIVATION_NAMES:
            raise ValueError(f"unknown activation {a!r}; "
                             f"known: {sorted(ACTIVATION_NAMES)}")
    return acts


@dataclasses.dataclass(frozen=True)
class LayeredPopulation:
    """P independent deep MLPs with HETEROGENEOUS depths fused into one
    layered layout.

    ``widths[m]`` is member m's per-hidden-layer width tuple (any length ≥ 1);
    ``activations[m]`` is either one name (used for every layer) or a tuple of
    names, one per hidden layer.  The population depth is the maximum member
    depth; a member of depth d < depth occupies, in every layer l ≥ d, a slice
    of its FINAL width that is carried through unchanged (identity weight, no
    bias, identity activation) — an exact structural pass-through, so fused
    training of mixed-depth members equals standalone training (DESIGN.md §3).
    """

    in_features: int
    out_features: int
    widths: tuple          # tuple[tuple[int, ...]] — per member, per layer
    activations: tuple     # tuple[tuple[str, ...]] — per member, per layer
    block: int = 8
    n_pad: int = 0         # trailing shard-pad members (see shard_pad)

    def __post_init__(self):
        if len(self.widths) != len(self.activations):
            raise ValueError(
                f"widths ({len(self.widths)}) and activations "
                f"({len(self.activations)}) must have the same length")
        if not self.widths:
            raise ValueError("empty population")
        if not 0 <= self.n_pad < len(self.widths):
            raise ValueError(f"n_pad {self.n_pad} out of range "
                             f"[0, {len(self.widths)})")
        widths = tuple(tuple(int(h) for h in w) for w in self.widths)
        for m, w in enumerate(widths):
            if len(w) < 1:
                raise ValueError(f"member {m}: needs at least one hidden layer")
            for h in w:
                if h < 1:
                    raise ValueError(f"member {m}: hidden size must be >= 1")
        acts = tuple(_normalise_member_acts(a, len(w), m)
                     for m, (a, w) in enumerate(zip(self.activations, widths)))
        object.__setattr__(self, "widths", widths)
        object.__setattr__(self, "activations", acts)

    # ------------------------------------------------------------------ #
    # constructors                                                       #
    # ------------------------------------------------------------------ #
    @staticmethod
    def grid(in_features: int, out_features: int,
             layer_widths: Sequence[Sequence[int]],
             activations: Sequence[str], repeats: int = 1, block: int = 8,
             sort_members: bool = True) -> "LayeredPopulation":
        """Architecture-search grid: every widths-tuple × activation pair,
        repeated — the deep generalisation of ``Population.grid`` (the paper's
        §7 pool of deep candidates).  ``layer_widths`` entries may have
        different lengths (heterogeneous depths)."""
        widths, acts = [], []
        for a in activations:
            for w in layer_widths:
                for _ in range(repeats):
                    widths.append(tuple(int(h) for h in w))
                    acts.append(a)
        lp = LayeredPopulation(in_features, out_features, tuple(widths),
                               tuple(acts), block=block)
        return lp.sorted() if sort_members else lp

    def sorted(self) -> "LayeredPopulation":
        """Reorder members so equal-shape members are contiguous: buckets per
        projection collapse to one run per (depth, padded widths, acts)
        class.  Shard-pad members stay trailing (their position is part of
        the sharding contract — callers exclude them by slicing [-n_pad:])."""
        def key(m):
            return (len(self.widths[m]),
                    tuple(_round_up(h, self.block) for h in self.widths[m]),
                    self.activations[m], self.widths[m])
        n_real = self.num_members - self.n_pad
        order = sorted(range(n_real), key=key) + list(
            range(n_real, self.num_members))
        return dataclasses.replace(
            self,
            widths=tuple(self.widths[m] for m in order),
            activations=tuple(self.activations[m] for m in order))

    # ------------------------------------------------------------------ #
    # per-layer layouts                                                  #
    # ------------------------------------------------------------------ #
    @property
    def num_members(self) -> int:
        return len(self.widths)

    @property
    def num_real(self) -> int:
        """Members that exist in the user's population (excludes trailing
        shard-pad filler members)."""
        return self.num_members - self.n_pad

    @cached_property
    def member_depths(self) -> tuple:
        return tuple(len(w) for w in self.widths)

    @property
    def depth(self) -> int:
        return max(self.member_depths)

    def layer_width(self, m: int, l: int) -> int:
        """Member m's width at layer l (its final width once passed-through)."""
        return self.widths[m][min(l, self.member_depths[m] - 1)]

    def layer_act(self, m: int, l: int) -> str:
        """Member m's activation at layer l (identity once passed-through)."""
        return self.activations[m][l] if l < self.member_depths[m] else "identity"

    @_instance_cache
    def layer_pop(self, l: int) -> Population:
        """The fused per-layer layout of hidden layer l (member order
        preserved; pass-through members keep their final-layer slot)."""
        if not 0 <= l < self.depth:
            raise ValueError(f"layer {l} out of range [0, {self.depth})")
        return Population(self.in_features, self.out_features,
                          tuple(self.layer_width(m, l)
                                for m in range(self.num_members)),
                          tuple(self.layer_act(m, l)
                                for m in range(self.num_members)),
                          block=self.block)

    def proj_real(self, m: int, l: int) -> bool:
        """True iff member m has a REAL weight in projection l (layer l→l+1)."""
        return l + 1 < self.member_depths[m]

    @_instance_cache
    def proj_buckets(self, l: int):
        """Buckets of projection l: (m0, n, hin, hout, off_in, off_out, real)
        runs, where ``real`` marks trained weight blocks vs identity
        pass-throughs (hin == hout there by construction).  Shard-pad
        members never merge into a real member's bucket (the pad flag is
        part of the run key), so the REAL buckets — runs, shapes, order —
        are identical with and without padding: ``pad_params`` can embed an
        unpadded parameter tree leaf-for-leaf."""
        pin, pout = self.layer_pop(l), self.layer_pop(l + 1)
        flags = tuple((self.proj_real(m, l), m >= self.num_real)
                      for m in range(self.num_members))
        return tuple(run + (flags[run[0]][0],)
                     for run in pin.pair_buckets(pout, keys=flags))

    @_instance_cache
    def active_unit_mask(self, l: int) -> np.ndarray:
        """1.0 for fused units of layer l belonging to members whose layer l
        is REAL (depth > l), 0.0 for pass-through slices.  Gates the mid-layer
        bias so pass-through members receive no bias (and no bias gradient)."""
        pop = self.layer_pop(l)
        mask = np.zeros(pop.total_hidden, dtype=np.float32)
        for m in range(self.num_members):
            if self.member_depths[m] > l:
                mask[pop.offsets[m]: pop.offsets[m + 1]] = 1.0
        return mask

    @_instance_cache
    def bd_layout(self, l: int) -> BlockDiagLayout:
        """Scalar-prefetch metadata for running projection l as ONE Pallas
        segment-blocked matmul (see BlockDiagLayout)."""
        pin, pout = self.layer_pop(l), self.layer_pop(l + 1)
        blk = self.block
        P = self.num_members
        ib = (pin.padded_sizes // blk).astype(int)
        ob = (pout.padded_sizes // blk).astype(int)
        in_t0 = (pin.offsets // blk).astype(int)
        out_t0 = (pout.offsets // blk).astype(int)
        real = [self.proj_real(m, l) for m in range(P)]

        base = np.zeros(P, dtype=int)
        acc = 0
        for m in range(P):
            base[m] = acc
            if real[m]:
                acc += ob[m] * ib[m]
        n_param = acc
        ident = n_param                       # shared identity tile (appended)

        n_out_tiles = int(out_t0[P])
        n_in_tiles = int(in_t0[P])

        def ragged_steps(transposed: bool):
            """Flattened (output tile, reduction k) step arrays: one grid
            step per REAL MXU tile of work (the ragged-grid fix — no dead
            k steps for narrow members or pass-through tiles).  ``qs`` maps
            each step to the PARAM tile whose (du, x) pair is live at that
            step (ident = the discarded dummy slot for pass-through) — the
            transposed orientation's qs is what lets the fused backward
            emit dw in the same pass as dx."""
            s_in, s_w, s_out, first, last, qs = [], [], [], [], [], []
            for m in range(P):
                n_o, n_i = (ib[m], ob[m]) if transposed else (ob[m], ib[m])
                rd0 = (out_t0 if transposed else in_t0)[m]
                wr0 = (in_t0 if transposed else out_t0)[m]
                for r in range(n_o):
                    t = wr0 + r
                    if real[m]:
                        for k in range(n_i):
                            s_in.append(rd0 + k)
                            s_w.append(base[m] + r * n_i + k)
                            s_out.append(t)
                            first.append(1 if k == 0 else 0)
                            last.append(1 if k == n_i - 1 else 0)
                            qs.append(base[m] + (k * n_o + r if transposed
                                                 else r * n_i + k))
                    else:
                        s_in.append(rd0 + r)
                        s_w.append(ident)
                        s_out.append(t)
                        first.append(1)
                        last.append(1)
                        qs.append(ident)
            return s_in, s_w, s_out, first, last, qs

        s_in, s_w, s_out, s_first, s_last, _ = ragged_steps(False)
        (s_in_t, s_w_t, s_out_t, s_first_t, s_last_t,
         s_q_t) = ragged_steps(True)

        perm = np.zeros(n_param + 1, int)
        perm[n_param] = n_param
        wb_out_tile = np.zeros(n_param, int)
        wb_in_tile = np.zeros(n_param, int)
        for m in range(P):
            if real[m]:
                for r in range(ob[m]):
                    for c in range(ib[m]):
                        q = base[m] + r * ib[m] + c
                        perm[base[m] + c * ob[m] + r] = q
                        wb_out_tile[q] = out_t0[m] + r
                        wb_in_tile[q] = in_t0[m] + c

        ints = lambda a: tuple(int(v) for v in a)
        return BlockDiagLayout(
            block=blk, n_in_tiles=n_in_tiles, n_out_tiles=n_out_tiles,
            n_param_blocks=n_param,
            n_steps=len(s_out), s_in=ints(s_in), s_w=ints(s_w),
            s_out=ints(s_out), s_first=ints(s_first), s_last=ints(s_last),
            n_steps_t=len(s_out_t), s_in_t=ints(s_in_t), s_w_t=ints(s_w_t),
            s_out_t=ints(s_out_t), s_first_t=ints(s_first_t),
            s_last_t=ints(s_last_t), s_q_t=ints(s_q_t),
            perm_t=ints(perm),
            wb_out_tile=ints(wb_out_tile), wb_in_tile=ints(wb_in_tile))

    # ------------------------------------------------------------------ #
    # sharding (DESIGN.md §5: the population axis IS the 'model' axis)   #
    # ------------------------------------------------------------------ #
    def shard_pad(self, n_shards: int) -> "LayeredPopulation":
        """Append filler members so the layout divides an ``n_shards``-way
        population axis: member count ≡ 0 (mod n_shards) and every layer's
        fused hidden axis ≡ 0 (mod n_shards·block), i.e. each shard holds
        whole member-aligned blocks.  Fillers are depth-``depth`` identity-
        activation members appended AFTER the real members (trailing, so
        member-major arrays slice them off with [:num_real]); they train but
        are excluded from selection.  Idempotent when already divisible.

        Per-bucket member counts are NOT forced to divide — a bucket whose
        run doesn't split evenly degrades to replication through
        ``filter_spec`` (the documented fallback)."""
        if n_shards <= 1:
            return self
        blk, L = self.block, self.depth
        hidden = [self.layer_pop(l).total_hidden for l in range(L)]
        mod = n_shards * blk
        d = (-self.num_members) % n_shards
        if d == 0 and all(h % mod == 0 for h in hidden):
            return self
        if d == 0:
            d = n_shards          # hidden axes still need fixing
        # d-1 minimal (width=block) fillers; the LAST filler's per-layer
        # width absorbs each layer's remaining misalignment.  Solvable
        # because every quantity involved is a multiple of block.
        base = ((blk,) * L,) * (d - 1)
        last = []
        for l in range(L):
            h = hidden[l] + (d - 1) * blk
            c = 1
            while (h + c * blk) % mod:
                c += 1
                assert c <= mod // blk + 1, "shard_pad: no aligning width"
            last.append(c * blk)
        widths = self.widths + base + (tuple(last),)
        acts = self.activations + (("identity",) * L,) * d
        return dataclasses.replace(self, widths=widths, activations=acts,
                                   n_pad=self.n_pad + d)

    def _sort_key(self, m: int):
        """The member-ordering key ``sorted()`` uses — exposed so growth
        can insert new members at their sorted-merge position."""
        return (len(self.widths[m]),
                tuple(_round_up(h, self.block) for h in self.widths[m]),
                self.activations[m], self.widths[m])

    def grow_positions(self, widths, activations) -> tuple:
        """Insert positions (strictly increasing indices into the GROWN
        layout) that place each new ``(widths, activations)`` member at its
        sorted-merge slot: after every existing member whose sort key is <=
        its own, so a sorted layout stays sorted after :meth:`grow` and
        equal-shape buckets merge instead of fragmenting.  Relative order
        among equal-key new members follows the given order (stable).  If
        the existing real members are NOT sorted, new members simply append
        at the end (still a valid grow — just more buckets)."""
        acts = tuple(_normalise_member_acts(a, len(tuple(w)), j)
                     for j, (w, a) in enumerate(zip(widths, activations)))
        widths = tuple(tuple(int(h) for h in w) for w in widths)
        old_keys = [self._sort_key(m) for m in range(self.num_real)]
        if any(old_keys[i] > old_keys[i + 1]
               for i in range(len(old_keys) - 1)):
            return tuple(self.num_real + j for j in range(len(widths)))

        def key(j):
            return (len(widths[j]),
                    tuple(_round_up(h, self.block) for h in widths[j]),
                    acts[j], widths[j])
        order = sorted(range(len(widths)), key=key)
        positions = [0] * len(widths)
        oi = 0                      # old members already passed
        placed = 0                  # new members already placed
        for j in order:
            while oi < len(old_keys) and old_keys[oi] <= key(j):
                oi += 1
            positions[j] = oi + placed
            placed += 1
        return tuple(positions)

    def grow(self, widths, activations, positions) -> "LayeredPopulation":
        """Fresh layout with new REAL members spliced in — the inverse of
        :meth:`subset` and the lifecycle's slot-refill primitive
        (core/lifecycle.py; DESIGN.md §13).

        ``positions[j]`` is the index INTO THE RESULT where new member ``j``
        lands (``grow_positions`` computes the sorted-merge placement);
        positions must be distinct but may pair new members in any order.
        Surviving members fill the complement in order, so
        ``grown.subset(complement) == self`` — grow-then-compact round-trips
        bit-exactly.  Growth happens on the REAL layout only (``n_pad`` must
        be 0 — compact first, grow, then re-``shard_pad``); the population
        depth extends automatically when a new member is deeper than every
        existing one (existing members ride the added layers as identity
        pass-throughs, exactly mirroring subset's depth shrink)."""
        if self.n_pad:
            raise ValueError(
                "grow: layout carries shard-pad fillers; grow the real "
                "layout (compact / subset first), then shard_pad the result")
        widths = tuple(tuple(int(h) for h in w) for w in widths)
        acts = tuple(_normalise_member_acts(a, len(w), j)
                     for j, (w, a) in enumerate(zip(widths, activations)))
        if len(widths) != len(acts) or not widths:
            raise ValueError("grow: need at least one new member, with one "
                             "activation spec per member")
        positions = tuple(int(p) for p in positions)
        if len(positions) != len(widths):
            raise ValueError(
                f"grow: {len(positions)} positions for {len(widths)} new "
                "members")
        n_total = self.num_real + len(widths)
        for p in positions:
            if not 0 <= p < n_total:
                raise ValueError(
                    f"grow: position {p} out of range [0, {n_total})")
        if len(set(positions)) != len(positions):
            raise ValueError(f"grow: duplicate positions in {positions}")
        pos_map = dict(zip(positions, range(len(widths))))
        out_w, out_a = [], []
        oi = 0
        for m in range(n_total):
            if m in pos_map:
                out_w.append(widths[pos_map[m]])
                out_a.append(acts[pos_map[m]])
            else:
                out_w.append(self.widths[oi])
                out_a.append(self.activations[oi])
                oi += 1
        return LayeredPopulation(self.in_features, self.out_features,
                                 tuple(out_w), tuple(out_a),
                                 block=self.block)

    def subset(self, keep) -> "LayeredPopulation":
        """Fresh layout of the given REAL members only — the lifecycle's
        compaction primitive (core/lifecycle.py; DESIGN.md §6).

        ``keep`` must be strictly increasing indices into the real members
        (shard-pad fillers cannot survive a rung; re-pad the result with
        ``shard_pad``).  Relative member order is preserved, so a sorted
        layout stays sorted and every derived quantity (offsets, buckets,
        bd_layout) is simply re-derived for the survivors: equal-shape runs
        that were split by a pruned member merge back into one bucket.  The
        population depth shrinks automatically when the deepest members are
        pruned (survivors were pass-through in the dropped layers, so the
        truncation is exact)."""
        keep = tuple(int(m) for m in keep)
        if not keep:
            raise ValueError("subset: empty keep set")
        prev = -1
        for m in keep:
            if not 0 <= m < self.num_real:
                raise ValueError(
                    f"subset: member {m} out of range [0, {self.num_real}) "
                    "(shard-pad fillers cannot survive)")
            if m <= prev:
                raise ValueError(
                    "subset: keep indices must be strictly increasing, got "
                    f"{keep}")
            prev = m
        return LayeredPopulation(
            self.in_features, self.out_features,
            tuple(self.widths[m] for m in keep),
            tuple(self.activations[m] for m in keep), block=self.block)

    def param_specs(self):
        """PartitionSpec tree matching ``deep.init_params``: every
        member-major axis shards over the population axis —

          w_in  (H0, F)        → P(pop, None)     b_in (H0,)   → P(pop)
          mid[l] w buckets (n, h_out, h_in) → P(pop, None, None) each
          mid[l] b (H_{l+1},)  → P(pop)
          w_out (O, H_last)    → P(None, pop)     b_out (P, O) → P(pop, None)

        Axes the ambient mesh lacks, or whose dim doesn't divide (e.g. a
        bucket run shorter than the axis), degrade to replication via
        ``filter_spec``; ``shard_pad`` makes the fused-hidden and member
        dims divide by construction."""
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import POP_AXIS
        mid = []
        for l in range(self.depth - 1):
            n_real_buckets = sum(1 for bk in self.proj_buckets(l) if bk[6])
            mid.append({"w": [P(POP_AXIS, None, None)] * n_real_buckets,
                        "b": P(POP_AXIS)})
        return {"w_in": P(POP_AXIS, None), "b_in": P(POP_AXIS), "mid": mid,
                "w_out": P(None, POP_AXIS), "b_out": P(POP_AXIS, None)}

    def opt_specs(self, opt, dtype=None):
        """Optimizer-state PartitionSpec tree for training this layout with
        ``opt`` (a ``repro.optim.Optimizer``): every state leaf inherits the
        sharding of the parameter it tracks."""
        import jax.numpy as jnp

        from repro.core.deep import abstract_params
        return opt.state_specs(
            self.param_specs(),
            abstract_params(self, dtype or jnp.float32))

    def describe(self) -> str:
        import collections
        by_depth = collections.Counter(self.member_depths)
        pad = f", pad={self.n_pad}" if self.n_pad else ""
        return (f"LayeredPopulation(P={self.num_members}{pad}, depth={self.depth}, "
                f"block={self.block}, in={self.in_features}, "
                f"out={self.out_features}, depths={dict(sorted(by_depth.items()))}, "
                f"fused_hidden={[self.layer_pop(l).total_hidden for l in range(self.depth)]})")
