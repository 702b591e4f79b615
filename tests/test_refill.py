"""Slot-refill search (DESIGN.md §13): layout growth is the bit-exact
inverse of compaction, constant-size refill rewrites pruned slots in place
with ZERO re-jit, refilled members get zero optimizer moments and fresh
ids (never a pruned seed's), and the --refill driver is deterministic
across resume — while --refill off stays bit-identical to the historical
halving driver."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import deep
from repro.core.lifecycle import (compact, compact_params, grow,
                                  grow_params, member_moment_mask,
                                  refill_params, refill_state)
from repro.core.population import LayeredPopulation
from repro.optim import adafactor, adamw, scale_member_moments, sgd
from repro.search import RefillController, SearchSpace

LP = LayeredPopulation(
    6, 3,
    widths=((7,), (13, 5), (64, 32, 16), (13, 5), (9,), (16, 8)),
    activations=("relu", ("tanh", "gelu"), ("mish", "sigmoid", "tanh"),
                 ("tanh", "gelu"), "relu", ("relu", "tanh")),
    block=8).sorted()

NEW_W = ((13, 5), (8,))
NEW_A = (("tanh", "gelu"), "relu")


def _tree_eq(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


def _all_zero(tree) -> bool:
    """True iff every NUMERIC leaf is zero (extract_member trees carry
    string metadata like the activation names)."""
    return all(not np.asarray(x).any() for x in jax.tree.leaves(tree)
               if np.issubdtype(np.asarray(x).dtype, np.number))


# --------------------------------------------------------------------- #
# layout growth                                                         #
# --------------------------------------------------------------------- #

def test_grow_positions_keep_sorted_layout():
    positions = LP.grow_positions(NEW_W, NEW_A)
    grown = LP.grow(NEW_W, NEW_A, positions)
    assert grown.num_real == LP.num_real + 2
    # sorted base stays sorted after the merge placement
    assert grown == grown.sorted()
    # positions[j] carries new member j's architecture
    for j, p in enumerate(positions):
        assert grown.widths[p] == NEW_W[j]
        assert grown.activations[p] == (
            NEW_A[j] if isinstance(NEW_A[j], tuple)
            else (NEW_A[j],) * len(NEW_W[j]))
    # removing the grown positions reads back the original layout
    rest = tuple(m for m in range(grown.num_real)
                 if m not in set(positions))
    assert grown.subset(rest) == LP


def test_grow_validation():
    with pytest.raises(ValueError, match="shard-pad"):
        LP.shard_pad(4).grow(NEW_W, NEW_A, (0, 1))
    with pytest.raises(ValueError, match="duplicate"):
        LP.grow(NEW_W, NEW_A, (2, 2))
    with pytest.raises(ValueError, match="range"):
        LP.grow(NEW_W, NEW_A, (0, LP.num_real + 2))


@pytest.mark.parametrize("gather", ["host", "device"])
def test_grow_then_compact_roundtrip_bit_exact(gather):
    """The tentpole invariant: grow-then-compact is BIT-IDENTICAL to
    never growing (survivors), and the grown members carry exactly their
    fresh init — grow_params is the inverse of compact_params."""
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    positions = LP.grow_positions(NEW_W, NEW_A)
    grown = LP.grow(NEW_W, NEW_A, positions)
    fresh_lp = grown.subset(tuple(sorted(positions)))
    fresh = deep.init_params(jax.random.PRNGKey(9), fresh_lp)
    gp = grow_params(LP, grown, params, positions, fresh, gather=gather)
    # compact the grown tree back down to the survivors → original tree
    rest = tuple(m for m in range(grown.num_real)
                 if m not in set(positions))
    back = compact_params(grown, LP, gp, rest, gather=gather)
    _tree_eq(back, params)
    # born members == their fresh init, member by member
    for r, p in enumerate(sorted(positions)):
        _tree_eq(deep.extract_member(gp, grown, p),
                 deep.extract_member(fresh, fresh_lp, r))


def test_grow_unsorted_positions_pair_members_correctly():
    """grow_positions pairs positions[j] with new member j even when the
    sorted-merge places them OUT of tuple order — the splice must index
    the fresh tree by position rank, not tuple index."""
    # deeper-first arch order vs the sorted layout → descending positions
    w, a = NEW_W, NEW_A
    positions = LP.grow_positions(w, a)
    assert tuple(sorted(positions)) != positions  # exercises the rank map
    grown = LP.grow(w, a, positions)
    fresh_lp = grown.subset(tuple(sorted(positions)))
    fresh = deep.init_params(jax.random.PRNGKey(9), fresh_lp)
    gp = grow_params(LP, grown, params=deep.init_params(
        jax.random.PRNGKey(0), LP), positions=positions, fresh=fresh)
    for r, p in enumerate(sorted(positions)):
        _tree_eq(deep.extract_member(gp, grown, p),
                 deep.extract_member(fresh, fresh_lp, r))


def test_grow_params_rejects_mismatched_layout():
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    positions = LP.grow_positions(NEW_W, NEW_A)
    grown = LP.grow(NEW_W, NEW_A, positions)
    fresh = deep.init_params(jax.random.PRNGKey(9),
                             grown.subset(tuple(sorted(positions))))
    wrong = tuple(m for m in range(len(positions)))
    if set(wrong) != set(positions):
        with pytest.raises(ValueError, match="grow"):
            grow_params(LP, grown, params, wrong, fresh)


@pytest.mark.parametrize("make_opt", [
    lambda: sgd(), lambda: sgd(momentum=0.9),
    lambda: adamw(weight_decay=0.01)])
def test_grow_state_zero_moments_survivors_bit_exact(make_opt):
    """Grown opt state: every newborn's moments are ZERO (what opt.init
    gives a fresh member), survivors' moments and the scalar count ride
    through bit-exact — for every params-shaped-subtree optimizer."""
    opt = make_opt()
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    state = opt.init(params)
    # fabricate non-zero moments so zeros are meaningful
    state = jax.tree.map(
        lambda x: x + 1 if x.ndim else x, state)
    positions = LP.grow_positions(NEW_W, NEW_A)
    grown = LP.grow(NEW_W, NEW_A, positions)
    gst = deep.grow_state(state, LP, grown, positions)
    assert int(gst["count"]) == int(state["count"])
    rest = tuple(m for m in range(grown.num_real)
                 if m not in set(positions))
    for key in state:
        if key == "count":
            continue
        for i, m in enumerate(rest):
            _tree_eq(deep.extract_member(gst[key], grown, m),
                     deep.extract_member(state[key], LP, i))
        for p in positions:
            assert _all_zero(deep.extract_member(gst[key], grown, p))


def test_grow_state_rejects_factored_adafactor():
    """Factored v_row/v_col reduce over the fused axis and cannot be
    spliced member-major — grow_state must fail LOUDLY (the driver
    carries adafactor momentum via compact_factored + grow_params)."""
    opt = adafactor()
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    state = opt.init(params)
    positions = LP.grow_positions(NEW_W, NEW_A)
    grown = LP.grow(NEW_W, NEW_A, positions)
    with pytest.raises(ValueError, match="grow_state"):
        deep.grow_state(state, LP, grown, positions)


def test_grow_orchestrator_end_to_end():
    """lifecycle.grow: params + opt state in one call, fresh init from the
    key, zero moments for the newborns."""
    opt = sgd(momentum=0.9)
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    state = jax.tree.map(lambda x: x + 1 if x.ndim else x,
                         opt.init(params))
    positions = LP.grow_positions(NEW_W, NEW_A)
    new_pop, new_p, new_st = grow(LP, params, state, NEW_W, NEW_A,
                                  positions, jax.random.PRNGKey(9))
    assert new_pop == LP.grow(NEW_W, NEW_A, positions)
    fresh_lp = new_pop.subset(tuple(sorted(positions)))
    fresh = deep.init_params(jax.random.PRNGKey(9), fresh_lp)
    for r, p in enumerate(sorted(positions)):
        _tree_eq(deep.extract_member(new_p, new_pop, p),
                 deep.extract_member(fresh, fresh_lp, r))
        assert _all_zero(deep.extract_member(new_st["mu"], new_pop, p))


# --------------------------------------------------------------------- #
# constant-size in-place refill                                         #
# --------------------------------------------------------------------- #

def _dup_slots(lp):
    """(slot, parent) for the fixture's duplicated (13, 5) architecture."""
    pair = [m for m in range(lp.num_real) if lp.widths[m] == (13, 5)]
    assert len(pair) == 2
    return pair


@pytest.mark.parametrize("gather", ["host", "device"])
def test_refill_params_in_place(gather):
    """One clone + one fresh refill: survivors' bytes untouched, the clone
    equals its parent bit-exact, the fresh slot equals its init — and the
    LAYOUT is the same object-equal dataclass (zero re-jit key)."""
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    slot_c, parent = _dup_slots(LP)
    slot_f = next(m for m in range(LP.num_real)
                  if m not in (slot_c, parent))
    fresh_lp = LayeredPopulation(
        LP.in_features, LP.out_features, (LP.widths[slot_f],),
        (LP.activations[slot_f],), block=LP.block)
    fresh = deep.init_params(jax.random.PRNGKey(9), fresh_lp)
    out = refill_params(LP, params, ((slot_c, parent), (slot_f, -1)),
                        fresh, gather=gather)
    for m in range(LP.num_real):
        if m in (slot_c, slot_f):
            continue
        _tree_eq(deep.extract_member(out, LP, m),
                 deep.extract_member(params, LP, m))
    _tree_eq(deep.extract_member(out, LP, slot_c),
             deep.extract_member(params, LP, parent))
    _tree_eq(deep.extract_member(out, LP, slot_f),
             deep.extract_member(fresh, fresh_lp, 0))


def test_refill_params_host_equals_device():
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    slot_c, parent = _dup_slots(LP)
    out_d = refill_params(LP, params, ((slot_c, parent),), gather="device")
    out_h = refill_params(LP, params, ((slot_c, parent),), gather="host")
    for a, b in zip(jax.tree.leaves(out_d), jax.tree.leaves(out_h)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_refill_params_validation():
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    slot_c, parent = _dup_slots(LP)
    with pytest.raises(ValueError, match="duplicate"):
        refill_params(LP, params, ((slot_c, parent), (slot_c, -1)))
    with pytest.raises(ValueError, match="surviving"):
        refill_params(LP, params, ((slot_c, parent), (parent, slot_c)))
    mismatch = next(m for m in range(LP.num_real)
                    if LP.widths[m] != LP.widths[slot_c])
    with pytest.raises(ValueError, match="arch"):
        refill_params(LP, params, ((slot_c, mismatch),))
    with pytest.raises(ValueError, match="fresh"):
        refill_params(LP, params, ((slot_c, -1),))
    with pytest.raises(ValueError, match="range"):
        refill_params(LP, params, ((LP.num_real, parent),))


@pytest.mark.parametrize("make_opt", [
    lambda: sgd(), lambda: sgd(momentum=0.9),
    lambda: adamw(weight_decay=0.01), lambda: adafactor()])
def test_refill_state_zero_moments_all_optimizers(make_opt):
    """refill_state zeroes the refilled slots' member-major moments for
    ALL FOUR optimizers — including adafactor, where the unfactorable m
    is masked per member and the factored v_row/v_col (which mix members
    over the fused axis) pass through bit-identical, re-warming like any
    post-rung adafactor state."""
    opt = make_opt()
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    state = jax.tree.map(lambda x: x + 1 if x.ndim else x,
                         opt.init(params))
    slot_c, parent = _dup_slots(LP)
    out = refill_state(state, LP, (slot_c,))
    assert int(out["count"]) == int(state["count"])
    if "leaves" in state:                      # adafactor
        def leaf_dicts(st):
            return [d for d in jax.tree.leaves(
                st["leaves"], is_leaf=lambda x: isinstance(x, dict)
                and ("v" in x or "v_row" in x))]
        for d_in, d_out in zip(leaf_dicts(state), leaf_dicts(out)):
            for k in ("v_row", "v_col"):
                if k in d_in:
                    np.testing.assert_array_equal(np.asarray(d_in[k]),
                                                  np.asarray(d_out[k]))
        return
    for key in state:
        if key == "count":
            continue
        assert _all_zero(deep.extract_member(out[key], LP, slot_c))
        for m in range(LP.num_real):
            if m == slot_c:
                continue
            _tree_eq(deep.extract_member(out[key], LP, m),
                     deep.extract_member(state[key], LP, m))


def test_member_moment_mask_matches_refill_state():
    """The mask is the mechanism: multiplying a moment tree by the keep
    mask equals refill_state's member-major zeroing."""
    opt = sgd(momentum=0.9)
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    state = jax.tree.map(lambda x: x + 1 if x.ndim else x,
                         opt.init(params))
    slot_c, _ = _dup_slots(LP)
    mask = member_moment_mask(LP, (slot_c,))
    via_scale = scale_member_moments(state, deep.abstract_params(LP), mask)
    _tree_eq(via_scale, refill_state(state, LP, (slot_c,)))


def test_refill_keeps_chunk_jaxpr_identical():
    """Zero re-jit, asserted at the jaxpr level: the refilled tree traces
    to the EXACT same program as the pre-refill tree (same layout → same
    shapes, dtypes, and jaxpr), so the driver's cached chunk callable is
    a guaranteed compile-cache hit."""
    opt = sgd(momentum=0.9)
    lp = LP.shard_pad(1)
    params = deep.init_params(jax.random.PRNGKey(0), lp)
    state = opt.init(params)
    chunk = deep.make_population_train_step(lp, optimizer=opt,
                                            scan_steps=2)
    xs = jnp.zeros((2, 4, lp.in_features))
    ys = jnp.zeros((2, 4), jnp.int32)
    jaxpr_before = str(jax.make_jaxpr(chunk)(params, state, xs, ys, 0.01))
    slot_c, parent = _dup_slots(lp)
    params2 = refill_params(lp, params, ((slot_c, parent),))
    state2 = refill_state(state, lp, (slot_c,))
    jaxpr_after = str(jax.make_jaxpr(chunk)(params2, state2, xs, ys, 0.01))
    assert jaxpr_before == jaxpr_after


# --------------------------------------------------------------------- #
# search space + controller                                             #
# --------------------------------------------------------------------- #

def test_search_space_parse_grammar():
    sp = SearchSpace.parse("widths=64,32|16,8;acts=relu,tanh;lr=0.5..2;"
                           "momentum=0.6..0.95;wd=0.4..2.5;"
                           "lr_perturb=0.9,1.1;momentum_jitter=0.02")
    assert sp.widths == ((64, 32), (16, 8))
    assert sp.acts == ("relu", "tanh")
    assert sp.lr_scale == (0.5, 2.0)
    assert sp.momentum_range == (0.6, 0.95)
    assert sp.wd_scale == (0.4, 2.5)
    assert sp.lr_perturb == (0.9, 1.1)
    assert sp.momentum_jitter == 0.02
    assert SearchSpace.parse(None) == SearchSpace()
    for bad in ("lr=3..0.3", "nope=1", "lr=0.3", "widths"):
        with pytest.raises(ValueError):
            SearchSpace.parse(bad)


def test_search_space_init_vectors_match_historical_draws():
    """The default space reproduces the driver's historical hardcoded
    per-member recipe draws BIT-FOR-BIT (the PR-8/9 trajectory
    invariant): same keys, same transform order, same ranges."""
    sp = SearchSpace()
    seed, n0, lr, wd = 3, 8, 0.01, 0.001
    np.testing.assert_array_equal(
        np.asarray(sp.init_lr(seed, n0, lr)),
        np.asarray(jnp.exp(jax.random.uniform(
            jax.random.PRNGKey(seed + 1), (n0,),
            minval=jnp.log(lr * 0.3), maxval=jnp.log(lr * 3.0)))))
    np.testing.assert_array_equal(
        np.asarray(sp.init_momentum(seed, n0)),
        np.asarray(jax.random.uniform(jax.random.PRNGKey(seed + 2),
                                      (n0,), minval=0.5, maxval=0.99)))
    np.testing.assert_array_equal(
        np.asarray(sp.init_wd(seed, n0, wd)),
        np.asarray(jnp.exp(jax.random.uniform(
            jax.random.PRNGKey(seed + 3), (n0,),
            minval=jnp.log(wd * 0.3), maxval=jnp.log(wd * 3.0)))))


def test_controller_plan_deterministic_and_exploit():
    losses = np.array([0.1, 0.9, 0.2, 0.8, 0.3, 0.7])
    keep = [0, 2, 4]
    ids = np.arange(LP.num_real)
    c = RefillController(SearchSpace(), mode="pbt", seed=7)
    lr = np.linspace(0.001, 0.006, LP.num_real)
    p1 = c.plan(LP, losses, keep, ids, rung=1, next_id=6, base_lr=0.01,
                lr=lr)
    p2 = c.plan(LP, losses, keep, ids, rung=1, next_id=6, base_lr=0.01,
                lr=lr)
    assert p1 == p2                           # resume-deterministic
    p3 = c.plan(LP, losses, keep, ids, rung=2, next_id=6, base_lr=0.01,
                lr=lr)
    assert [m.slot for m in p3.members] == [m.slot for m in p1.members]
    assert p1.slots == tuple(s for s in range(LP.num_real)
                             if s not in keep)
    for j, m in enumerate(p1.members):
        assert m.member_id == 6 + j           # fresh ids, never reused
        assert m.birth_rung == 1
        assert m.widths == LP.widths[m.slot]  # pbt adopts the slot arch
        if m.origin == "exploit":
            assert m.parent_slot in keep
            assert LP.widths[m.parent_slot] == LP.widths[m.slot]
            assert m.lr is not None and m.lr != lr[m.parent_slot]
        else:
            assert m.parent_slot == -1 and m.parent_id == -1
    # the fixture's duplicated (13, 5) arch: whichever of the pair is
    # pruned exploits the surviving twin
    pair = _dup_slots(LP)
    pruned_twin = [m for m in p1.members if m.slot in pair]
    assert pruned_twin and all(m.origin == "exploit" for m in pruned_twin)


def test_controller_arch_mode_needs_widths_menu():
    with pytest.raises(ValueError, match="widths"):
        RefillController(SearchSpace(), mode="arch")
    sp = SearchSpace.parse("widths=8,4|6")
    c = RefillController(sp, mode="arch", seed=0)
    plan = c.plan(LP, np.arange(6.0), [0, 1, 2], np.arange(6), rung=1,
                  next_id=6, base_lr=0.01)
    assert all(m.origin == "fresh" and m.widths in sp.widths
               for m in plan.members)


def test_refill_member_ids_never_alias(tmp_path):
    """selection's duplicate-id guard: a refilled member aliasing a pruned
    seed's id is an error, fresh monotone ids are accepted."""
    from repro.core.selection import leaderboard, member_metrics
    losses = np.linspace(1.0, 2.0, LP.num_real)
    with pytest.raises(ValueError, match="alias"):
        leaderboard(LP, losses, member_ids=[0, 1, 2, 2, 4, 5])
    with pytest.raises(ValueError, match="entries"):
        member_metrics(LP, losses, member_ids=[0, 1])
    lineage = {7: (2, 1)}
    rows = member_metrics(LP, losses, member_ids=[0, 1, 2, 7, 4, 5],
                          lineage=lineage)
    by_id = {r["member"]: r for r in rows}
    assert by_id[7]["lineage"] == {"member": 7, "parent": 2,
                                   "born_rung": 1}
    assert by_id[0]["lineage"] == {"member": 0, "parent": -1,
                                   "born_rung": 0}
    top = leaderboard(LP, losses, member_ids=[0, 1, 2, 7, 4, 5],
                      lineage=lineage, k=6)
    assert all("lineage" in r for r in top)


# --------------------------------------------------------------------- #
# data plane: signature-gated retarget                                  #
# --------------------------------------------------------------------- #

def test_retarget_keeps_staging_on_matching_signature():
    from repro.data import Prefetcher, staging_signature

    def make_staging():
        return (np.empty((2, 4, 3), np.float32), np.empty((2, 4), np.int32))

    def produce(c, staging):
        sx, sy = staging
        sx[...] = c
        return np.array(sx)

    pf = Prefetcher(produce, 4, make_staging=make_staging)
    ids0 = tuple(id(a) for a in pf._staging[0] + pf._staging[1])
    assert pf.get(0)[0, 0, 0] == 0
    sig = staging_signature(make_staging())
    pf.retarget(produce, 4, make_staging=make_staging, signature=sig)
    # same signature → the SAME staging buffers, not reallocations
    assert tuple(id(a) for a in pf._staging[0] + pf._staging[1]) == ids0
    assert pf.get(0)[0, 0, 0] == 0
    pf.close()


def test_retarget_rebuilds_staging_on_mismatch_or_none():
    from repro.data import Prefetcher

    def make_a():
        return np.empty((2, 4), np.float32)

    def make_b():
        return np.empty((2, 3), np.float32)  # shrinking rung: new shapes

    def produce_a(c, staging):
        staging[...] = c
        return np.array(staging)

    pf = Prefetcher(produce_a, 4, make_staging=make_a)
    ids0 = tuple(id(a) for a in pf._staging)
    # mismatched signature → rebuild with the NEW factory
    pf.retarget(produce_a, 4, make_staging=make_b,
                signature=(((2, 3), np.dtype(np.float32).str),))
    assert tuple(id(a) for a in pf._staging) != ids0
    assert pf._staging[0].shape == (2, 3)
    assert pf.get(0).shape == (2, 3)
    # omitted signature → conservative rebuild even with matching shapes
    ids1 = tuple(id(a) for a in pf._staging)
    pf.retarget(produce_a, 4, make_staging=make_b)
    assert tuple(id(a) for a in pf._staging) != ids1
    pf.close()


# --------------------------------------------------------------------- #
# driver: --refill end to end                                           #
# --------------------------------------------------------------------- #

_BASE = ["--arch", "parallelmlp-10k", "--reduced", "--scan-steps", "2",
         "--samples", "256", "--population-acts", "relu,tanh",
         "--population-depths", "8,4;8,4;6;5;12,6;7;9;10",
         "--per-member-lr", "--ckpt-every", "2",
         "--halving", "4:0.5,8:0.5"]
_REFILL = _BASE + ["--refill", "pbt"]


def test_refill_driver_constant_size_zero_rejit(tmp_path, capsys):
    """--refill pbt: population size constant through both rungs, every
    rung boundary is a chunk-cache hit, the whole 3-segment ladder
    compiles ONE chunk program, and the leaderboard reports lineage."""
    from repro.launch.train import main
    params, lp = main(_REFILL + ["--steps", "12",
                                 "--ckpt-dir", str(tmp_path / "ck")])
    assert lp.num_real == 8                   # prune 4 → refill 4, twice
    out = capsys.readouterr().out
    assert out.count("cache-hit (zero re-jit)") == 2
    assert "1 chunk builds" in out
    assert "explored 16 models" in out
    assert "born r" in out


def test_refill_driver_survivor_prefix_matches_plain_halving(tmp_path):
    """Up to the first refill rung the refill run IS the plain-halving
    run: at the boundary, every survivor's params in the refilled layout
    equal the compacted no-refill run's, bit for bit."""
    from repro.checkpoint import load_meta, restore_population
    from repro.launch.train import main
    main(_REFILL + ["--steps", "6", "--ckpt-dir", str(tmp_path / "rf")])
    main(_BASE + ["--steps", "6", "--ckpt-dir", str(tmp_path / "off")])
    # both force-saved their post-rung state at the boundary step (3)
    p_rf, lp_rf, _ = restore_population(str(tmp_path / "rf"), step=3)
    p_off, lp_off, _ = restore_population(str(tmp_path / "off"), step=3)
    meta_rf, _ = load_meta(str(tmp_path / "rf"))
    meta_off, _ = load_meta(str(tmp_path / "off"))
    ids_rf = meta_rf["lifecycle"]["member_ids"]
    ids_off = meta_off["lifecycle"]["member_ids"]
    assert lp_rf.num_real == 8 and lp_off.num_real == 4
    # seed ids == seed slots at the first rung: survivors sit at ids_off
    for i, mid in enumerate(ids_off):
        assert mid in ids_rf
        _tree_eq(deep.extract_member(p_rf, lp_rf, ids_rf.index(mid)),
                 deep.extract_member(p_off, lp_off, i))
    # refilled members carry FRESH ids above every seed id
    assert sorted(set(ids_rf) - set(ids_off))[0] >= 8


def test_refill_driver_resume_mid_ladder_bit_exact(tmp_path):
    """Stop between refill rungs, --resume: identical params, lineage,
    and recipe-vector tails to the uninterrupted run (the controller rng
    folds (seed, rung), the grown vectors ride the checkpoint meta)."""
    from repro.checkpoint import load_meta
    from repro.launch.train import main
    main(_REFILL + ["--steps", "6", "--ckpt-dir", str(tmp_path / "ck")])
    meta_a, _ = load_meta(str(tmp_path / "ck"))
    assert meta_a["lifecycle"]["rung"] == 1
    assert meta_a["lifecycle"]["next_id"] == 12
    p_res, lp_res = main(_REFILL + ["--steps", "12", "--resume",
                                    "--ckpt-dir", str(tmp_path / "ck")])
    p_str, lp_str = main(_REFILL + ["--steps", "12",
                                    "--ckpt-dir", str(tmp_path / "ck2")])
    assert lp_res == lp_str
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), p_res, p_str)
    meta_r, _ = load_meta(str(tmp_path / "ck"))
    meta_s, _ = load_meta(str(tmp_path / "ck2"))
    assert meta_r["lifecycle"] == meta_s["lifecycle"]
    assert meta_r["lifecycle"]["lineage"]      # newborns recorded


def test_refill_driver_arch_mode_grows_layout(tmp_path, capsys):
    """--refill arch: pruned slots are replaced by freshly SAMPLED
    architectures spliced into a grown layout."""
    from repro.launch.train import main
    params, lp = main(_BASE + [
        "--refill", "arch",
        "--search-space", "widths=8,4|6|10,5;acts=relu,tanh",
        "--steps", "12", "--ckpt-dir", str(tmp_path / "ck")])
    assert lp.num_real == 8                   # 8 -4 +4, twice
    out = capsys.readouterr().out
    assert out.count("grew 4 sampled archs") == 2
    menu = {(8, 4), (6,), (10, 5)}
    assert set(lp.widths) <= menu | {(5,), (7,), (9,), (10,), (12, 6),
                                     (8, 4), (6,)}


_REFILL_4DEV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
import jax, jax.numpy as jnp, numpy as np
from repro.core import deep
from repro.core.lifecycle import compact_params, grow_params
from repro.core.population import LayeredPopulation
from repro.distributed.sharding import population_shardings
from repro.launch.mesh import make_host_mesh
from jax import set_mesh

assert len(jax.devices()) == 4
LP = LayeredPopulation(
    6, 3,
    widths=((7,), (13, 5), (64, 32, 16), (13, 5), (9,), (16, 8)),
    activations=("relu", ("tanh", "gelu"), ("mish", "sigmoid", "tanh"),
                 ("tanh", "gelu"), "relu", ("relu", "tanh")),
    block=8).sorted()
NEW_W, NEW_A = ((13, 5), (8,)), (("tanh", "gelu"), "relu")
mesh = make_host_mesh()
with set_mesh(mesh):
    lp = LP.shard_pad(4)
    params = jax.device_put(deep.init_params(jax.random.PRNGKey(0), lp),
                            population_shardings(lp, mesh))
    # grow the REAL prefix: compact off the pad, splice, re-pad
    real = tuple(range(LP.num_real))
    p_real = compact_params(lp, LP, params, real, gather="device")
    positions = LP.grow_positions(NEW_W, NEW_A)
    grown = LP.grow(NEW_W, NEW_A, positions)
    fresh_lp = grown.subset(tuple(sorted(positions)))
    fresh = deep.init_params(jax.random.PRNGKey(9), fresh_lp)
    gp = grow_params(LP, grown, p_real, positions, fresh, gather="device")
    pad = grown.shard_pad(4)
    gp_pad = jax.device_put(deep.pad_params(gp, grown, pad,
                                            jax.random.PRNGKey(1)),
                            population_shardings(pad, mesh))
    # the born-sharded splice round-trips bit-exact on the 4-device mesh
    host = grow_params(LP, grown, jax.tree.map(np.asarray, p_real),
                       positions, jax.tree.map(np.asarray, fresh),
                       gather="host")
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), gp, host)
    for l in jax.tree.leaves(gp_pad):
        assert len(l.sharding.device_set) == 4
print("OK")
"""


@pytest.mark.slow
def test_grow_splice_on_4_device_mesh(tmp_path):
    """Born-sharded splice: device-gather growth on the 4-fake-device
    mesh equals the host path bit-exact, and the re-padded tree lands
    sharded across all 4 devices."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    r = subprocess.run([sys.executable, "-c", _REFILL_4DEV],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
