"""Streaming data plane (DESIGN.md §11): Prefetcher semantics (ordering,
backpressure, seek/retarget, producer-failure surfacing, clean shutdown),
DeferredMetrics laziness, slab-build value parity, and the driver-level
bit-identity contract — a pipelined run must reproduce the synchronous
run's params AND optimizer state exactly, with and without --halving."""
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.data import DeferredMetrics, PrefetchError, Prefetcher, TabularTask

# --------------------------------------------------------------------- #
# Prefetcher unit semantics                                             #
# --------------------------------------------------------------------- #


def test_prefetcher_orders_and_matches_sync():
    made = []

    def produce(c, staging):
        made.append(c)
        return c * 10

    with Prefetcher(produce, 8) as pf:
        got = [pf.get(c) for c in range(8)]
    assert got == [c * 10 for c in range(8)]
    assert made == list(range(8))


def test_prefetcher_get_past_end_raises():
    with Prefetcher(lambda c, s: c, 3) as pf:
        for c in range(3):
            pf.get(c)
        with pytest.raises(PrefetchError, match="past the end"):
            pf.get(3)


def test_prefetcher_backpressure_bounded():
    """The producer runs at most ``depth`` chunks ahead of the consumer
    before blocking on the bounded queue (+1 build may be in flight)."""
    made = []

    def produce(c, staging):
        made.append(c)
        return c

    with Prefetcher(produce, 100, depth=2) as pf:
        deadline = time.monotonic() + 5.0
        while len(made) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)          # would run away here if unbounded
        assert max(made) <= 3    # depth slabs queued + 1 build in flight
        pf.get(0)
        pf.get(1)
        deadline = time.monotonic() + 5.0
        while len(made) < 5 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert max(made) <= 5


def test_prefetcher_staging_alternates():
    """Consecutive chunks see the two distinct staging buffers
    alternately — chunk k+1 never builds into the buffer chunk k staged."""
    seen = []

    def produce(c, staging):
        seen.append(id(staging))
        return c

    with Prefetcher(produce, 6, make_staging=lambda: [0]) as pf:
        for c in range(6):
            pf.get(c)
    assert len(set(seen)) == 2
    assert all(a != b for a, b in zip(seen, seen[1:]))


def test_prefetcher_out_of_order_get_seeks():
    """A crash replay re-enters at an earlier chunk: get() re-syncs the
    producer instead of delivering stale slabs."""
    with Prefetcher(lambda c, s: c * 10, 10) as pf:
        assert pf.get(0) == 0
        assert pf.get(1) == 10
        assert pf.get(0) == 0       # replay from 0
        assert pf.get(1) == 10
        assert pf.get(5) == 50      # skip ahead
        assert pf.get(6) == 60


def test_prefetcher_producer_exception_surfaces_and_close_never_hangs():
    def produce(c, staging):
        if c == 2:
            raise RuntimeError("disk on fire")
        return c

    pf = Prefetcher(produce, 8)
    assert pf.get(0) == 0
    assert pf.get(1) == 1
    with pytest.raises(PrefetchError, match="disk on fire") as ei:
        pf.get(2)
    assert isinstance(ei.value.__cause__, RuntimeError)
    t0 = time.monotonic()
    pf.close()                      # dead producer: close must not hang
    pf.close()                      # idempotent
    assert time.monotonic() - t0 < 5.0


def test_prefetcher_close_unblocks_full_queue():
    """close() while the producer is blocked mid-put (queue full, consumer
    gone) joins the thread instead of hanging — the shutdown contract."""
    pf = Prefetcher(lambda c, s: np.zeros(4), 1000, depth=1)
    time.sleep(0.1)                 # let the producer fill + block
    t0 = time.monotonic()
    pf.close()
    assert time.monotonic() - t0 < 5.0
    assert threading.active_count() >= 1  # and no leaked thread hangs join


def test_prefetcher_blocked_put_wakes_fast_after_get():
    """The bounded put is a condition-variable hand-off, not a poll: a
    producer blocked on the full queue resumes producing within 10 ms of
    the consumer's get (a polling put — the pre-§12 implementation slept
    50 ms between stop-flag checks — fails this by construction)."""
    produced = {}

    def produce(c, staging):
        produced[c] = time.perf_counter()
        return c

    pf = Prefetcher(produce, 8, depth=1)
    try:
        # depth=1: chunk 0 fills the queue, chunk 1 is produced (and
        # timestamped) then blocks in put — so the hand-off we time is
        # chunk 2's production after the get drains a slot
        deadline = time.monotonic() + 5.0
        while 1 not in produced and time.monotonic() < deadline:
            time.sleep(0.001)
        assert 1 in produced, "producer never reached the blocking put"
        time.sleep(0.05)            # let it park on the full queue
        assert 2 not in produced, "producer was not actually blocked"
        t_get = time.perf_counter()
        assert pf.get(0) == 0
        deadline = time.monotonic() + 5.0
        while 2 not in produced and time.monotonic() < deadline:
            time.sleep(0.001)
        assert 2 in produced
        assert produced[2] - t_get < 0.010, (
            f"blocked put took {(produced[2] - t_get) * 1e3:.1f} ms to "
            "wake after the consumer get — backpressure is polling, not "
            "a condition hand-off")
    finally:
        pf.close()


def test_prefetcher_retarget_switches_source():
    """The rung-boundary protocol: retarget drops in-flight slabs and
    re-aims the producer at the new segment's builder/staging."""
    pf = Prefetcher(lambda c, s: ("old", c), 100)
    assert pf.get(0) == ("old", 0)
    pf.retarget(lambda c, s: ("new", c), 4, start=0)
    assert [pf.get(c) for c in range(4)] == [("new", c) for c in range(4)]
    with pytest.raises(PrefetchError):
        pf.get(4)
    pf.close()


def test_prefetcher_rejects_bad_depth():
    with pytest.raises(ValueError, match="depth"):
        Prefetcher(lambda c, s: c, 4, depth=0)


# --------------------------------------------------------------------- #
# DeferredMetrics                                                       #
# --------------------------------------------------------------------- #


def test_deferred_metrics_lazy_and_cached():
    calls = []

    def resolve():
        calls.append(1)
        return {"loss": 0.5, "step": 7}

    m = DeferredMetrics(resolve)
    assert not m.resolved and not calls   # storing costs nothing
    assert m["loss"] == 0.5               # first access resolves
    assert m.resolved and len(calls) == 1
    assert dict(m) == {"loss": 0.5, "step": 7}
    assert len(m) == 2 and "step" in m
    assert len(calls) == 1                # cached, not re-resolved
    assert "0.5" in repr(m)


# --------------------------------------------------------------------- #
# counters and spans                                                    #
# --------------------------------------------------------------------- #


def _host_events(trace_dir) -> list:
    """``(name, thread, stats)`` of every host event of the one profiler
    trace under ``trace_dir``; ``thread`` is the event's line index."""
    import pathlib

    from jax.profiler import ProfileData
    path, = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    return [(e.name, i, dict(e.stats))
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for i, line in enumerate(plane.lines) for e in line.events]


def test_prefetcher_stats_count_builds_and_gets():
    with Prefetcher(lambda c, s: c, 5) as pf:
        for c in range(3):
            pf.get(c)
        deadline = time.monotonic() + 5.0
        while pf.stats["built"] < 5 and time.monotonic() < deadline:
            time.sleep(0.01)
    st = pf.stats
    assert (st["built"], st["got"]) == (5, 3)
    assert st["build_s"] >= 0 and st["wait_s"] >= 0
    assert 0 <= st["starved"] <= 3


def test_prefetcher_slow_producer_starves_and_records_wait(tmp_path):
    """A get that finds the queue empty counts ``starved`` and opens a
    ``prefetch.wait`` span on the consumer's thread; the build it waits
    for is a ``prefetch.build`` span of the same chunk on the producer's."""
    def produce(c, staging):
        time.sleep(0.05)
        return c

    with jax.profiler.trace(str(tmp_path)), Prefetcher(produce, 1) as pf:
        assert pf.get(0) == 0
    st = pf.stats
    assert (st["built"], st["got"], st["starved"]) == (1, 1, 1)
    assert st["wait_s"] > 0.02 and st["build_s"] > 0.04
    ev = _host_events(tmp_path)
    wait = [(t, a) for n, t, a in ev if n == "prefetch.wait"]
    build = [(t, a) for n, t, a in ev if n == "prefetch.build"]
    assert [a["chunk"] for _, a in wait] == [0]
    assert [a["chunk"] for _, a in build] == [0]
    assert wait[0][0] != build[0][0]


def test_prefetcher_full_queue_records_no_wait(tmp_path):
    with Prefetcher(lambda c, s: c, 4, depth=2) as pf:
        deadline = time.monotonic() + 5.0
        while not pf._q.full() and time.monotonic() < deadline:
            time.sleep(0.01)
        with jax.profiler.trace(str(tmp_path)):
            assert [pf.get(0), pf.get(1)] == [0, 1]
    assert pf.stats["starved"] == 0 and pf.stats["wait_s"] == 0.0
    assert not [n for n, _t, _a in _host_events(tmp_path)
                if n == "prefetch.wait"]


def test_deferred_metrics_resolve_span_once(tmp_path):
    m = DeferredMetrics(lambda: {"loss": 0.5})
    with jax.profiler.trace(str(tmp_path)):
        assert m.force(chunk=7) == {"loss": 0.5}
        assert m["loss"] == 0.5          # cached: no second span
    spans = [a for n, _t, a in _host_events(tmp_path)
             if n == "metrics.resolve"]
    assert spans == [{"chunk": 7}]


# --------------------------------------------------------------------- #
# slab builds: value parity with per-step batch()                       #
# --------------------------------------------------------------------- #


def test_batch_slab_value_identical_to_per_step_batches():
    """batch_slab (the §11 producer build, epoch permutation amortized)
    must produce byte-identical values to stacking batch(step) — across
    epoch boundaries, wrap-around tails, and via caller staging."""
    for n, b in [(1000, 128), (256, 128), (300, 100)]:
        t = TabularTask(n, 7, n_classes=3, seed=5)
        per_epoch = max(n // b, 1)
        start, steps = max(per_epoch - 2, 0), 3 * per_epoch + 4
        ref_x = np.stack([t.batch(start + j, b)[0] for j in range(steps)])
        ref_y = np.stack([t.batch(start + j, b)[1] for j in range(steps)])
        sx, sy = t.batch_slab(start, steps, b)
        np.testing.assert_array_equal(sx, ref_x)
        np.testing.assert_array_equal(sy, ref_y)
        ox = np.empty_like(sx)
        oy = np.empty_like(sy)
        rx, _ = t.batch_slab(start, steps, b, out=(ox, oy))
        assert rx is ox
        np.testing.assert_array_equal(ox, ref_x)
        np.testing.assert_array_equal(oy, ref_y)


# --------------------------------------------------------------------- #
# driver bit-identity: --pipeline on == off                             #
# --------------------------------------------------------------------- #


def _drive(tmp_path, tag, pipeline, extra=()):
    from repro.launch.train import main
    return main([
        "--arch", "parallelmlp-10k", "--reduced", "--steps", "8",
        "--ckpt-every", "4", "--ckpt-dir", str(tmp_path / tag),
        "--population-depths", "8,4;8,4;6;5", "--population-acts",
        "relu,tanh", "--scan-steps", "2", "--samples", "256",
        "--pipeline", "on" if pipeline else "off", *extra])


def _final_ckpt_arrays(tmp_path, tag):
    import repro.checkpoint as ckpt_mod
    step = ckpt_mod.latest_steps(str(tmp_path / tag))[-1]
    return np.load(os.path.join(str(tmp_path / tag),
                                f"step_{step:08d}", "arrays.npz"))


def _assert_bit_identical(pa, pb):
    import jax
    leaves_a, leaves_b = jax.tree.leaves(pa), jax.tree.leaves(pb)
    assert len(leaves_a) == len(leaves_b)
    for a, b in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_pipeline_bit_identical_plain(tmp_path):
    pa, lpa = _drive(tmp_path, "on", True)
    pb, lpb = _drive(tmp_path, "off", False)
    assert lpa == lpb
    _assert_bit_identical(pa, pb)


@pytest.mark.slow
def test_pipeline_bit_identical_halving_with_opt_state(tmp_path):
    """Across halving rung boundaries (prefetcher retarget + re-jit) the
    pipelined trajectory still matches synchronous exactly — params AND
    the momentum optimizer state in the final checkpoint."""
    extra = ["--optimizer", "momentum", "--halving", "2:0.5,4:0.5"]
    pa, lpa = _drive(tmp_path, "on", True, extra)
    pb, lpb = _drive(tmp_path, "off", False, extra)
    assert lpa == lpb and lpa.num_real == 1
    _assert_bit_identical(pa, pb)
    za = _final_ckpt_arrays(tmp_path, "on")
    zb = _final_ckpt_arrays(tmp_path, "off")
    assert sorted(za.files) == sorted(zb.files)
    extras = [k for k in za.files if k.startswith("extra/")]
    assert any(k.startswith("extra/mu/") for k in extras)
    for k in za.files:
        np.testing.assert_array_equal(za[k], zb[k], err_msg=k)


@pytest.mark.slow
def test_pipeline_bit_identical_adafactor_halving(tmp_path):
    """Adafactor + --halving now composes (factored stats re-initialized
    per rung, momentum carried): pipelined == synchronous, and the ladder
    prunes to one member."""
    extra = ["--optimizer", "adafactor", "--weight-decay", "0.001",
             "--halving", "2:0.5,4:0.5"]
    pa, lpa = _drive(tmp_path, "on", True, extra)
    pb, lpb = _drive(tmp_path, "off", False, extra)
    assert lpa == lpb and lpa.num_real == 1
    _assert_bit_identical(pa, pb)
    za = _final_ckpt_arrays(tmp_path, "on")
    zb = _final_ckpt_arrays(tmp_path, "off")
    for k in za.files:
        np.testing.assert_array_equal(za[k], zb[k], err_msg=k)


def test_trace_dir_traces_chunks_one_to_three(tmp_path, capsys):
    """``--trace-dir`` writes a profiler trace of chunks 1-3 of the first
    segment: ``train_chunk`` steps, the data plane's spans and the
    metric fetches, tagged with their chunk; each segment's end prints
    the prefetcher's counters."""
    _drive(tmp_path, "traced", True,
           ("--trace-dir", str(tmp_path / "trace"), "--steps", "16",
            "--halving", "12:0.5"))
    ev = _host_events(tmp_path / "trace")
    steps = sorted(a["step_num"] for n, _t, a in ev if n == "train_chunk")
    assert steps == [1, 2, 3]
    resolved = sorted(a["chunk"] for n, _t, a in ev
                      if n == "metrics.resolve")
    assert resolved and set(resolved) <= {0, 1, 2, 3}
    # chunk 4's slab is built while chunks 1-3 run (chunk 1's get frees
    # its queue slot)
    assert 4 in [a["chunk"] for n, _t, a in ev if n == "prefetch.build"]
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.startswith("prefetch: ")]
    assert len(lines) == 2               # one per segment
    assert "built" in lines[0] and "starved" in lines[0]


# --------------------------------------------------------------------- #
# 4-fake-device: slabs land with population_batch_shardings             #
# --------------------------------------------------------------------- #

_SHARDED_PIPELINE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
import jax, numpy as np
import repro.data.pipeline as pl

seen = []
orig_get = pl.Prefetcher.get

def spy(self, c, timeout=600.0):
    slab = orig_get(self, c, timeout)
    seen.append(tuple(a.sharding for a in slab))
    return slab

pl.Prefetcher.get = spy

from repro.launch.train import main
params, lp = main([
    "--arch", "parallelmlp-10k", "--reduced", "--steps", "6",
    "--population-depths", "16,8;12,4;7;9", "--population-acts",
    "relu,tanh", "--scan-steps", "3", "--ckpt-every", "0",
    "--pipeline", "on", "--ckpt-dir", sys.argv[1] + "/ck"])
assert len(jax.devices()) == 4
assert seen, "prefetcher never delivered a slab"

from repro.distributed.sharding import population_batch_shardings
from repro.launch.mesh import make_host_mesh
sh_x, sh_y = population_batch_shardings(make_host_mesh(), 8)
for shx, shy in seen:
    assert shx == sh_x, (shx, sh_x)
    assert shy == sh_y, (shy, sh_y)
print("OK", len(seen))
"""


@pytest.mark.slow
def test_pipeline_slabs_carry_population_batch_shardings(tmp_path):
    """On a 4-fake-device mesh the prefetcher's device slabs arrive with
    exactly the shardings population_batch_shardings prescribes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    r = subprocess.run([sys.executable, "-c", _SHARDED_PIPELINE,
                        str(tmp_path)],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
