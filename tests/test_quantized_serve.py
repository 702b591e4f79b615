"""Int8 weight-only serving path (DESIGN.md §12): the quantized serve
copy (``quant.quantize_population``) through the fused-dequant forward
kernels, plus the serving-engine semantics that ride on it.

  numerics — the int8 forward is BIT-EXACT against the dequantized-
             reference tree run through the committed f32 path (the
             kernels' in-loop ``q·scale`` must equal the host-side
             dequant), and bounded-error against the f32 masters;
  budget   — ``forward(infer=True, weights_dtype="int8")`` keeps the
             depth+1 single-output launch contract;
  routing  — the int8 path is reachable ONLY via ``weights_dtype`` at
             serving time; every wrong spelling fails loudly;
  shared scale math — ``distributed.compression.quantize_int8`` now
             composes the ``repro.quant`` helpers: op sequence (and so
             the compressed all-reduce) bit-identical to the original
             inline formula;
  engine   — ``PopulationServer`` quantizes ONCE (masters released),
             and ``run``'s accounting: partial-slab max-latency,
             warmup excluded from p50/p99, members_served under a
             published subset and a filler-padded layout.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import deep
from repro.core.activations import ACTIVATION_ORDER
from repro.core.ensemble import real_slots
from repro.core.population import LayeredPopulation
from repro.launch.launch_count import (count_pallas_launches,
                                       fused_infer_budget, max_eqn_outputs)
from repro.launch.serve_population import PopulationServer
from repro.quant import (dequantize_population, quantize_population,
                         serve_copy_bytes)
from _kernel_routing import kernel_names

_WIDTHS = ((5, 3), (12, 9), (7,), (17, 9, 5), (8, 8),
           (5, 3), (3, 11, 2), (24, 16), (4,), (9, 9, 9))
LP = LayeredPopulation(6, 3, _WIDTHS, ACTIVATION_ORDER, block=8)
B = 9


def _params(lp=LP, seed=0):
    return deep.init_params(jax.random.PRNGKey(seed), lp)


def _x(b=B, lp=LP):
    return jax.random.normal(jax.random.PRNGKey(1), (b, lp.in_features))


def _infer_int8(qp, x, lp=LP, **kw):
    return deep.forward(qp, x, lp, bd_impl="fused", infer=True,
                        weights_dtype="int8", **kw)


def _ref(params, x, lp=LP):
    return deep.forward(params, x, lp, bd_impl="einsum")


# --------------------------------------------------------------------- #
# packer: tree layout + round-trip error bound                          #
# --------------------------------------------------------------------- #


def test_quantize_population_tree_layout():
    qp = quantize_population(_params(), LP)
    blk = LP.block
    h0 = LP.layer_pop(0).total_hidden
    assert qp["w_in"].dtype == jnp.int8
    assert qp["w_in"].shape[0] == h0
    assert qp["w_in"].shape[1] % 8 == 0          # pre-padded feature axis
    assert qp["w_in_scale"].shape == (h0 // blk,)
    for l, layer in enumerate(qp["mid"]):
        n = LP.bd_layout(l).n_param_blocks
        assert layer["wb"].dtype == jnp.int8
        # identity tile pre-augmented at quantize time, scale 1.0
        assert layer["wb"].shape == (n + 1, blk, blk)
        assert layer["scale"].shape == (n + 1,)
        np.testing.assert_array_equal(np.asarray(layer["wb"][-1]),
                                      np.eye(blk, dtype=np.int8))
        assert float(layer["scale"][-1]) == 1.0
    hl = LP.layer_pop(LP.depth - 1).total_hidden
    assert qp["w_out"].dtype == jnp.int8
    assert qp["w_out"].shape == (LP.out_features, hl)
    assert qp["w_out_scale"].shape == (hl // blk,)
    # weight-only: every bias stays full-precision
    for b in (qp["b_in"], qp["b_out"], *(m["b"] for m in qp["mid"])):
        assert b.dtype == jnp.float32
    # the weight bytes shrink 4x; on this tiny layout biases/scales eat
    # into the ratio, so assert the conservative half bound here (the
    # --quant bench records the real ratio on the bench population)
    assert serve_copy_bytes(qp) < serve_copy_bytes(_params()) / 2


def test_dequantize_round_trip_error_bound():
    """Symmetric per-tile int8: |x - dq(q(x))| <= scale/2, and scale is
    the tile max over 127 — so the global bound is max|leaf| / 254."""
    params = _params()
    dq = dequantize_population(quantize_population(params, LP), LP)
    flat_p, _ = jax.tree.flatten(params)
    flat_d, _ = jax.tree.flatten(dq)
    for a, b in zip(flat_p, flat_d):
        bound = float(jnp.max(jnp.abs(a))) / 254.0 + 1e-6
        assert float(jnp.max(jnp.abs(a - b))) <= bound


# --------------------------------------------------------------------- #
# numerics: fused dequant == host dequant, bit for tolerance            #
# --------------------------------------------------------------------- #


def test_int8_forward_matches_dequant_reference():
    """The kernels' in-loop q·scale must reproduce the host-side
    dequantized tree exactly (same f32 ops, same order) — compared
    through the independent einsum reference path."""
    params, x = _params(), _x()
    qp = quantize_population(params, LP)
    got = _infer_int8(qp, x)
    want = _ref(dequantize_population(qp, LP), x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_int8_forward_bounded_error_vs_f32_masters():
    params, x = _params(), _x()
    y_f32 = deep.forward(params, x, LP, bd_impl="fused", infer=True)
    y_q = _infer_int8(quantize_population(params, LP), x)
    np.testing.assert_allclose(y_q, y_f32, rtol=0.1, atol=0.5)


def test_int8_log_probs_in_kernel():
    params, x = _params(), _x()
    qp = quantize_population(params, LP)
    got = _infer_int8(qp, x, log_probs=True)
    want = jax.nn.log_softmax(_ref(dequantize_population(qp, LP), x),
                              axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.exp(got).sum(-1), 1.0, rtol=1e-5)


def test_int8_on_shard_padded_layout():
    lpp = LP.shard_pad(4)
    assert lpp.num_members > real_slots(lpp)
    params = _params(lpp)
    x = _x(lp=lpp)
    qp = quantize_population(params, lpp)
    np.testing.assert_allclose(
        _infer_int8(qp, x, lpp), _ref(dequantize_population(qp, lpp), x, lpp),
        rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------- #
# launch budget under int8                                              #
# --------------------------------------------------------------------- #


def test_int8_keeps_infer_launch_budget():
    params, x = _params(), _x()
    qp = quantize_population(params, LP)

    def fwd(p):
        return _infer_int8(p, x)

    budget = fused_infer_budget(LP.depth)
    assert count_pallas_launches(fwd, qp) == budget["total"]
    assert max_eqn_outputs(fwd, qp) == 1


# --------------------------------------------------------------------- #
# routing: the int8 path only via weights_dtype, loud-fail otherwise    #
# --------------------------------------------------------------------- #


def test_int8_requires_infer():
    qp = quantize_population(_params(), LP)
    with pytest.raises(ValueError, match="serving-only"):
        deep.forward(qp, _x(), LP, bd_impl="fused", weights_dtype="int8")


def test_int8_not_selectable_as_bd_impl():
    with pytest.raises(ValueError, match="bd_impl"):
        deep.forward(_params(), _x(), LP, bd_impl="fused_int8", infer=True)


def test_int8_head_impl_must_match():
    """The int8 store serves only through the int8 kernels — the head
    included — and only on the fused path."""
    qp = quantize_population(_params(), LP)
    assert kernel_names(lambda p: _infer_int8(p, _x()), qp) == {
        "fused_input_infer_int8", "fused_mid_infer_int8", "infer_head_int8"}
    with pytest.raises(ValueError, match="bd_impl='fused'"):
        deep.forward(qp, _x(), LP, bd_impl="einsum", infer=True,
                     weights_dtype="int8")


def test_unknown_weights_dtype_rejected():
    with pytest.raises(ValueError, match="weights_dtype"):
        deep.forward(_params(), _x(), LP, bd_impl="fused", infer=True,
                     weights_dtype="int4")


# --------------------------------------------------------------------- #
# shared scale math: compression.quantize_int8 regression               #
# --------------------------------------------------------------------- #


def test_quantize_int8_bit_identical_to_inline_formula():
    """The gradient compressor now composes ``repro.quant`` helpers; the
    result (q, scale, error-feedback residual) must be BIT-identical to the
    pre-refactor inline formula — so the compressed all-reduce stream is
    unchanged."""
    from repro.distributed.compression import quantize_int8
    g = jax.random.normal(jax.random.PRNGKey(2), (513,)) * 3.7
    err = jax.random.normal(jax.random.PRNGKey(3), (513,)) * 0.01
    # the original inline op sequence, verbatim
    gf = g.astype(jnp.float32) + err
    scale = jnp.max(jnp.abs(gf)) / 127.0 + 1e-12
    q_ref = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    err_ref = gf - q_ref.astype(jnp.float32) * scale
    q, s, e = quantize_int8(g, err)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    assert float(s) == float(scale)
    np.testing.assert_array_equal(np.asarray(e), np.asarray(err_ref))


# --------------------------------------------------------------------- #
# serving engine: quantize-once + run() accounting                      #
# --------------------------------------------------------------------- #


def _calib(lp, n=32, seed=4):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, lp.in_features))
    y = jax.random.randint(jax.random.PRNGKey(seed + 1), (n,), 0,
                           lp.out_features)
    return x, y


def test_server_quantizes_once_and_serves_int8():
    server = PopulationServer(_params(), LP, weights_dtype="int8",
                              batch=8, topk=2, max_latency_ms=5.0)
    assert server.check_budget()["launches"] == LP.depth + 1
    # after the first consumer touched params, ONLY the int8 copy remains
    assert server.params["w_in"].dtype == jnp.int8
    qp = server.params
    xc, yc = _calib(LP)
    board = server.publish(xc, yc)
    assert server.params is qp                   # no re-quantization
    assert len(server.published["topk"]) == 2
    r = server.run(np.asarray(_x(16)), "topk")
    assert r["members_served"] == 2
    assert r["pred"].shape == (16,)
    assert set(np.unique(r["pred"])) <= set(range(LP.out_features))
    assert board[0]["rank"] == 1


def test_server_refresh_requantizes():
    server = PopulationServer(_params(), LP, weights_dtype="int8",
                              batch=8, topk=2)
    server.check_budget()
    assert server.params["w_in"].dtype == jnp.int8
    server.refresh(_params(seed=5), LP)
    assert server.params["w_in"].dtype == jnp.float32   # new masters
    server.check_budget()
    assert server.params["w_in"].dtype == jnp.int8      # re-quantized


def _fake_server(lp, *, batch, max_latency_ms, first_call_sleep=0.0):
    """A server whose per-mode steps are instant host functions — isolates
    ``run``'s batching/latency accounting from kernel wall-clock."""
    server = PopulationServer(_params(lp), lp, batch=batch,
                              max_latency_ms=max_latency_ms)
    state = {"calls": 0}

    def fake_step(params, xb):
        state["calls"] += 1
        if state["calls"] == 1 and first_call_sleep:
            time.sleep(first_call_sleep)     # stands in for jit compile
        b = xb.shape[0]
        return {"pred": jnp.zeros(b, jnp.int32),
                "probs": jnp.zeros((b, lp.out_features), jnp.float32),
                "mutual_information": jnp.zeros(b, jnp.float32)}

    for m in ("all", "topk", "best1"):
        server._steps[m] = fake_step
    return server, state


def test_run_partial_slab_pays_max_latency():
    """A timer-fired partial slab's requests record the max-latency wait;
    a full slab's do not."""
    server, _ = _fake_server(LP, batch=8, max_latency_ms=200.0)
    xs = np.zeros((4, LP.in_features), np.float32)     # one partial slab
    r = server.run(xs, "all", warmup=False)
    assert r["p50_ms"] >= 200.0 and r["p99_ms"] >= 200.0
    server, _ = _fake_server(LP, batch=8, max_latency_ms=200.0)
    r_full = server.run(np.zeros((8, LP.in_features), np.float32), "all",
                        warmup=False)
    assert r_full["p99_ms"] < 200.0                    # flushed on fill


def test_run_warmup_excluded_from_percentiles():
    """The warmup slab runs before the clock starts, so first-call cost
    (compilation) never lands in p50/p99."""
    server, state = _fake_server(LP, batch=4, max_latency_ms=1.0,
                                 first_call_sleep=0.25)
    r = server.run(np.zeros((8, LP.in_features), np.float32), "all",
                   warmup=True)
    assert state["calls"] == 3                         # warmup + 2 slabs
    assert r["p99_ms"] < 200.0
    server, _ = _fake_server(LP, batch=4, max_latency_ms=1.0,
                             first_call_sleep=0.25)
    r = server.run(np.zeros((8, LP.in_features), np.float32), "all",
                   warmup=False)
    assert r["p99_ms"] >= 200.0                        # cost hit a request


def test_run_members_served_accounting():
    """members_served: the published subset's size per mode; 'all' counts
    REAL members only on a filler-padded layout."""
    lpp = LP.shard_pad(4)
    assert lpp.num_members > real_slots(lpp)
    server, _ = _fake_server(lpp, batch=4, max_latency_ms=1.0)
    server.published = {"all": None, "topk": [0, 3, 5], "best1": [2]}
    xs = np.zeros((4, lpp.in_features), np.float32)
    assert server.run(xs, "all", warmup=False)["members_served"] \
        == real_slots(lpp)
    assert server.run(xs, "topk", warmup=False)["members_served"] == 3
    assert server.run(xs, "best1", warmup=False)["members_served"] == 1
