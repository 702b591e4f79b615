"""Static HLO cost model: must agree with XLA on loop-free dot flops and
apply trip-count weighting that XLA's cost_analysis lacks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import analyze, parse_module
from repro.launch.hlo_stats import collective_stats, shape_bytes


def test_loop_free_dot_matches_xla():
    def f(a, b):
        return (a @ b).sum()

    A = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    B = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    c = jax.jit(f).lower(A, B).compile()
    mine = analyze(c.as_text())
    want = 2 * 64 * 128 * 32
    assert abs(mine["flops"] - want) / want < 0.01
    xla = c.cost_analysis()["flops"]
    assert abs(mine["flops"] - xla) / xla < 0.05


def test_scan_trip_count_weighting():
    L = 7

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=L)
        return y.sum()

    X = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    W = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(f).lower(X, W).compile()
    mine = analyze(c.as_text())
    want = 2 * 32 * 64 * 64 * L
    assert abs(mine["flops"] - want) / want < 0.01
    assert any(n == L for _, n in mine["loops"])
    # XLA undercounts exactly by the trip count
    xla = c.cost_analysis()["flops"]
    assert mine["flops"] > xla * (L - 1) / 2


def test_nested_scans_multiply():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y.sum()

    X = jax.ShapeDtypeStruct((16, 32), jnp.float32)
    W = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    c = jax.jit(f).lower(X, W).compile()
    mine = analyze(c.as_text())
    want = 2 * 16 * 32 * 32 * 15
    assert abs(mine["flops"] - want) / want < 0.01


def test_shape_bytes_parser():
    assert shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert shape_bytes("bf16[8]") == 16
    assert shape_bytes("(f32[4,4], s32[2])") == 64 + 8
    assert shape_bytes("pred[]") == 1
    assert shape_bytes("token[]") == 0


def test_parse_module_handles_wrapped_lines():
    hlo = """HloModule test
%comp (a: (s32[],
  f32[4,4])) -> f32[4,4] {
  %p = (s32[], /*index=1*/
    f32[4,4]) parameter(0)
  ROOT %r = f32[4,4]{1,0} get-tuple-element(%p), index=1
}
ENTRY %main (x: f32[4,4]) -> f32[4,4] {
  %x.1 = f32[4,4]{1,0} parameter(0)
  ROOT %c = f32[4,4]{1,0} copy(%x.1)
}
"""
    comps = parse_module(hlo)
    assert "comp" in comps and "main" in comps
    assert any(op.opcode == "copy" for op in comps["main"])


def test_collective_stats_regex():
    hlo = """
  %ag = f32[64,128]{1,0} all-gather(%x), dimensions={0}
  %ar = bf16[256]{0} all-reduce(%y), to_apply=%add
  %rs = f32[32]{0} reduce-scatter(%z), dimensions={0}
  %a2a = f32[16,16]{1,0} all-to-all(%w), dimensions={0}
  %cp = f32[8]{0} collective-permute(%v), source_target_pairs={{0,1}}
"""
    st = collective_stats(hlo)
    assert st["counts"] == {"all-gather": 1, "all-reduce": 1,
                            "reduce-scatter": 1, "all-to-all": 1,
                            "collective-permute": 1}
    assert st["per_device_bytes"]["all-gather"] == 64 * 128 * 4
    assert st["per_device_bytes"]["all-reduce"] == 256 * 2
