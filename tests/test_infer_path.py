"""The forward-only serving path (DESIGN.md §10): ``deep.forward(infer=
True)`` routes every fused impl to its residual-free twin and the output
projection through the one-launch infer head.  Three invariant families:

  numerics — infer logits match the einsum reference bit-for-tolerance
             across ALL activations, under the bf16 policy, on ragged and
             shard-padded layouts, with and without in-kernel log-softmax;
  budget   — exactly depth+1 Pallas launches, every one single-output (a
             2-output launch means a residual survived), at any batch;
  fillers  — shard_pad identity fillers can never leak into an ensemble
             reduction: the member axis is sliced to ``num_real`` before
             any mean/argmax, and explicit member sets naming a filler
             slot fail loudly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import deep
from repro.core.activations import ACTIVATION_ORDER
from repro.core.ensemble import (ENSEMBLE_MODES, _validate_slots, best_member,
                                 disagreement, ensemble_predict,
                                 member_log_probs, real_slots, soft_vote)
from repro.core.population import LayeredPopulation, Population
from repro.core.selection import (evaluate_population, leaderboard,
                                  member_metrics)
from repro.launch.launch_count import (count_pallas_launches,
                                       fused_infer_budget, max_eqn_outputs)
from _kernel_routing import kernel_names

# one member per activation — the reference sweep covers the whole table
_WIDTHS = ((5, 3), (12, 9), (7,), (17, 9, 5), (8, 8),
           (5, 3), (3, 11, 2), (24, 16), (4,), (9, 9, 9))
LP = LayeredPopulation(6, 3, _WIDTHS, ACTIVATION_ORDER, block=8)
B = 9


def _params(lp=LP, seed=0):
    return deep.init_params(jax.random.PRNGKey(seed), lp)


def _x(b=B, lp=LP):
    return jax.random.normal(jax.random.PRNGKey(1), (b, lp.in_features))


def _infer(params, x, lp=LP, **kw):
    return deep.forward(params, x, lp, bd_impl="fused", infer=True, **kw)


def _ref(params, x, lp=LP):
    return deep.forward(params, x, lp, bd_impl="einsum")


# --------------------------------------------------------------------- #
# numerics                                                              #
# --------------------------------------------------------------------- #


def test_infer_matches_einsum_all_activations():
    """Forward-only fused path vs the pure-XLA reference, one member per
    activation in the table."""
    params, x = _params(), _x()
    np.testing.assert_allclose(_infer(params, x), _ref(params, x),
                               rtol=1e-5, atol=1e-6)


def test_infer_log_probs_in_kernel():
    """``log_probs=True`` folds the log-softmax into the head epilogue —
    same launch count, log-probabilities out."""
    params, x = _params(), _x()
    got = _infer(params, x, log_probs=True)
    want = jax.nn.log_softmax(_ref(params, x), axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.exp(got).sum(-1), 1.0, rtol=1e-5)


def test_infer_xla_head_routing():
    """The head follows ``bd_impl``: the fused serving forward ends in the
    infer-head kernel after the forward-only layer kernels, the einsum
    serving forward is all XLA (the bucketed M3 head) — numerics equal."""
    params, x = _params(), _x()
    assert kernel_names(lambda p: _infer(p, x), params) == {
        "fused_input_infer", "fused_mid_infer", "infer_head"}
    xla = deep.forward(params, x, LP, bd_impl="einsum", infer=True)
    assert kernel_names(lambda p: deep.forward(
        p, x, LP, bd_impl="einsum", infer=True), params) == set()
    np.testing.assert_allclose(xla, _infer(params, x), rtol=1e-5, atol=1e-6)


def test_infer_rejects_unknown_head():
    """Serving has no implementation of its own: a name other than the
    two paths is refused, the removed ``pallas`` included."""
    for bad in ("nope", "pallas"):
        with pytest.raises(ValueError, match="einsum.*fused"):
            deep.forward(_params(), _x(), LP, bd_impl=bad, infer=True)


def test_infer_bf16_policy():
    """The mixed-precision policy applies to the infer path too: bf16
    operands, f32 accumulators/bias/logits."""
    params, x = _params(), _x()
    got = _infer(params, x, compute_dtype="bfloat16")
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, _ref(params, x), rtol=1e-1, atol=5e-2)


@pytest.mark.parametrize("widths,acts", [
    (((24,), (13, 5), (17, 9), (32, 16, 8)),
     ("relu", "tanh", "gelu", "sigmoid")),
    (((3,), (3,), (31, 2)), ("identity", "mish", "elu")),
], ids=["mixed_depth", "tiny_ragged"])
def test_infer_ragged_layouts(widths, acts):
    lp = LayeredPopulation(7, 4, widths, acts, block=8)
    params = _params(lp)
    x = _x(11, lp)
    np.testing.assert_allclose(_infer(params, x, lp), _ref(params, x, lp),
                               rtol=1e-5, atol=1e-6)


def test_infer_on_shard_padded_layout():
    """The kernels compute filler slots like any member (real arrays, no
    special cases) — every slot, filler included, matches the reference."""
    lpp = LP.shard_pad(4)
    assert lpp.num_members > real_slots(lpp)
    params = _params(lpp)
    x = _x(lp=lpp)
    np.testing.assert_allclose(_infer(params, x, lpp), _ref(params, x, lpp),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------- #
# launch budget / no-residual assertion                                 #
# --------------------------------------------------------------------- #


def test_infer_budget_formula():
    assert fused_infer_budget(1) == {"fwd": 2, "total": 2}
    assert fused_infer_budget(3) == {"fwd": 4, "total": 4}


@pytest.mark.parametrize("b", [9, 1024], ids=["small_b", "large_b"])
def test_infer_budget_batch_independent(b):
    """depth+1 launches at B=9 AND B=1024 — and every pallas_call is
    single-output: no residual buffer exists anywhere in the program."""
    params = _params()
    x = jnp.zeros((b, LP.in_features))

    def fwd(p):
        return _infer(p, x)

    assert count_pallas_launches(fwd, params) == \
        fused_infer_budget(LP.depth)["total"]
    assert max_eqn_outputs(fwd, params) == 1


def test_train_reuse_keeps_residuals_alive():
    """The counter-example the infer path exists for: serving off the
    training step's VJP-forward leaves 2-output pallas_calls (logits +
    g' residual) in the jaxpr."""
    params = _params()
    x = _x()

    def reuse(p):
        return jax.vjp(lambda q: deep.forward(
            q, x, LP, bd_impl="fused"), p)[0]

    assert max_eqn_outputs(reuse, params) == 2


def test_infer_log_probs_same_budget():
    params = _params()

    def fwd(p):
        return _infer(p, _x(), log_probs=True)

    assert count_pallas_launches(fwd, params) == \
        fused_infer_budget(LP.depth)["total"]


# --------------------------------------------------------------------- #
# ensemble reductions + the filler-exclusion invariant                  #
# --------------------------------------------------------------------- #


def _poisoned_padded_logits():
    """Real logits from the unpadded population, with filler rows set to
    a value that would wreck any reduction that sees them."""
    lpp = LP.shard_pad(4)
    nr = real_slots(lpp)
    logits = _infer(_params(), _x())
    assert logits.shape[1] == nr
    poison = jnp.full((B, lpp.num_members - nr, logits.shape[-1]), 1e30)
    return jnp.concatenate([logits, poison], axis=1), logits, lpp


def test_fillers_never_reach_reductions():
    """Regression for the shard_pad leak: reductions over the padded
    layout equal reductions over the unpadded one, poison and all."""
    lg_pad, lg, lpp = _poisoned_padded_logits()
    np.testing.assert_allclose(soft_vote(lg_pad, lpp), soft_vote(lg, LP),
                               rtol=1e-6)
    for k, v in disagreement(lg_pad, lpp).items():
        np.testing.assert_allclose(v, disagreement(lg, LP)[k], rtol=1e-5,
                                   err_msg=k)
        assert np.all(np.isfinite(np.asarray(v))), k
    out = ensemble_predict(lg_pad, lpp, "all", with_uncertainty=True)
    np.testing.assert_allclose(
        out["probs"], ensemble_predict(lg, LP, "all")["probs"], rtol=1e-6)


def test_filler_slots_fail_loudly():
    lg_pad, _, lpp = _poisoned_padded_logits()
    nr = real_slots(lpp)
    with pytest.raises(ValueError, match="filler"):
        best_member(lg_pad, lpp, nr)          # first filler slot
    with pytest.raises(ValueError, match="filler"):
        soft_vote(lg_pad, lpp, member_ids=[0, nr])
    with pytest.raises(ValueError, match="filler"):
        ensemble_predict(lg_pad, lpp, "topk", member_ids=[1, lpp.num_members - 1])
    with pytest.raises(ValueError, match="empty"):
        _validate_slots([], nr)


def test_ensemble_modes_and_shapes():
    logits = _infer(_params(), _x())
    assert ENSEMBLE_MODES == ("best1", "topk", "all")
    for mode, ids in (("best1", [3]), ("topk", [3, 0, 7]), ("all", None)):
        out = ensemble_predict(logits, LP, mode, member_ids=ids,
                               with_uncertainty=True)
        assert out["probs"].shape == (B, LP.out_features)
        assert out["pred"].shape == (B,)
        np.testing.assert_allclose(out["probs"].sum(-1), 1.0, rtol=1e-5)
        assert np.all(np.asarray(out["mutual_information"]) > -1e-5)
    with pytest.raises(ValueError, match="member_ids"):
        ensemble_predict(logits, LP, "best1")


def test_reductions_accept_logits_or_log_probs():
    """softmax is shift-invariant per row, so the head may emit either."""
    logits = _infer(_params(), _x())
    logp = member_log_probs(logits)
    np.testing.assert_allclose(soft_vote(logits, LP), soft_vote(logp, LP),
                               rtol=1e-5)
    np.testing.assert_allclose(best_member(logits, LP, 2),
                               best_member(logp, LP, 2), rtol=1e-5)


def test_weighted_soft_vote():
    logits = _infer(_params(), _x())
    # weight mass entirely on member 4 == best_member(4)
    np.testing.assert_allclose(
        soft_vote(logits, LP, member_ids=[4, 6], weights=[1.0, 0.0]),
        best_member(logits, LP, 4), rtol=1e-6)
    with pytest.raises(ValueError, match="weights"):
        soft_vote(logits, LP, member_ids=[4, 6], weights=[1.0])


# --------------------------------------------------------------------- #
# selection: infer-path eval routing, leaderboard sort_by, metrics rows #
# --------------------------------------------------------------------- #


def test_eval_routes_through_infer_path():
    """``evaluate_population(infer=True)`` scores on the serving kernels
    and must agree with the training-path eval to f32 tolerance."""
    params = _params()
    x = _x(64)
    y = jax.random.randint(jax.random.PRNGKey(2), (64,), 0, LP.out_features)
    l_ref, a_ref = evaluate_population(params, LP, x, y)
    l_inf, a_inf = evaluate_population(params, LP, x, y, bd_impl="fused",
                                       infer=True)
    np.testing.assert_allclose(l_inf, l_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(a_inf, a_ref, rtol=1e-6)


def test_single_layer_population_rejects_infer():
    pop = Population(6, 3, (5, 9), ("relu", "tanh"), block=8)
    from repro.core import parallel_mlp as pmlp
    params = pmlp.init_params(jax.random.PRNGKey(0), pop)
    x = _x(8)
    y = jnp.zeros((8,), jnp.int32)
    with pytest.raises(ValueError, match="infer"):
        evaluate_population(params, pop, x, y, infer=True)


def test_leaderboard_sort_by_acc():
    losses = np.linspace(0.1, 1.0, LP.num_members)
    accs = np.linspace(0.0, 0.9, LP.num_members)   # best acc = last member
    by_loss = leaderboard(LP, losses, accs, k=3)
    by_acc = leaderboard(LP, losses, accs, k=3, sort_by="acc")
    assert by_loss[0]["slot"] == 0
    assert by_acc[0]["slot"] == LP.num_members - 1
    assert by_acc[0]["acc"] == pytest.approx(0.9)
    with pytest.raises(ValueError, match="acc"):
        leaderboard(LP, losses, None, sort_by="acc")
    with pytest.raises(ValueError, match="sort_by"):
        leaderboard(LP, losses, accs, sort_by="vibes")


def test_member_metrics_rows():
    lpp = LP.shard_pad(4)
    losses = np.arange(lpp.num_members, dtype=np.float64)
    rows = member_metrics(lpp, losses)
    assert len(rows) == real_slots(lpp)            # fillers excluded
    for m, row in enumerate(rows):
        assert row["slot"] == m
        assert row["depth"] == len(_WIDTHS[m])
        assert row["hidden"] == _WIDTHS[m]
        assert row["loss"] == pytest.approx(float(losses[m]))
