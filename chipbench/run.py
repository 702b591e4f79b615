#!/usr/bin/env python3
"""The chip benchmark: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the checkout root on a machine that holds the cell's chips.  The
cell, its configuration, traffic mix and limits, the per-layer metrics'
readers and each layer's trace names are found by name in
``BENCHMARK.json`` and under ``chipbench/``.  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.  Every run
checks the first chunk, run by the window's own executable, against the
plain float32 reference (``reference.py``) and prints each compared number
beside its limit, as the last lines on standard error and under
``checks`` in the result line, the last line of standard output.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".jax_cache"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _say(msg):
    print(msg, file=sys.stderr, flush=True)


def load_reader(bench_dir: pathlib.Path, name: str):
    """``read(ctx)`` of ``metrics/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", bench_dir / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def layer_patterns(bench_dir: pathlib.Path) -> dict:
    """``{layer: [trace-name patterns]}``: per layer directory, the union
    of its files' ``patterns``."""
    out = {}
    for d in sorted((bench_dir / "layers").iterdir()):
        pats = []
        for f in sorted(d.glob("*.json")):
            pats += json.loads(f.read_text())["patterns"]
        out[d.name] = sorted(set(pats))
    return out


def cell_metrics(bench: dict, name: str, group: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or name in m["workloads"]]


def device_info(devices, peak_bytes=None) -> dict:
    d = {"platform": devices[0].platform, "kind": devices[0].device_kind,
         "count": len(devices)}
    if peak_bytes is not None:
        d["memory_peak_bytes"] = peak_bytes
    return d


def run(args, *, bench_dir=BENCH, root=ROOT, devices=None, wrap=None,
        trace_dir=None) -> dict:
    """One run → the result object.  ``devices`` and ``wrap`` (see
    ``Cell.setup``) let the tests drive a run on the CPU with a planted
    fault; ``trace_dir`` keeps the trace."""
    import jax

    import cell as cellmod
    import flops
    from devtrace import Trace

    bench = json.loads((root / "BENCHMARK.json").read_text())
    spec = cellmod.load(bench_dir, bench, args.workload)
    cfg = spec["cfg"]
    if devices is None:
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise SystemExit(f"needs a TPU; JAX found {devices[0].platform}")
        if len(devices) < spec["chips"]:
            raise SystemExit(f"{args.workload} needs {spec['chips']} chips; "
                             f"JAX sees {len(devices)}")
        devices = devices[:spec["chips"]]
        peaks = json.loads((bench_dir / "peaks.json").read_text())["devices"]
        if devices[0].device_kind not in peaks:
            raise SystemExit(f"no peaks for {devices[0].device_kind!r} in "
                             "peaks.json")
        peak = peaks[devices[0].device_kind]
    else:
        peak = None
    t_cell = time.perf_counter()
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        c = cellmod.Cell(spec, devices)
        layout_s = time.perf_counter() - t_cell
        with jax.set_mesh(c.mesh):
            c.setup(args.seed, wrap=wrap)
            setup_s = time.perf_counter() - T_START
            _say(f"set-up {setup_s:.3f} s: to the cell {t_cell - T_START:.3f}"
                 f", layout {layout_s:.3f}, " + ", ".join(
                     f"{k} {v:.3f}" for k, v in c.phases.items()))
            tdir = None
            if args.trace:
                tdir = trace_dir or tempfile.mkdtemp(prefix="chipbench_")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                win = c.window(args.seconds)
            finally:
                if tdir:
                    jax.profiler.stop_trace()
            stats = [d.memory_stats() or {} for d in devices]
            peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
            c.free()
        t_ref = time.perf_counter()
        nums = c.numbers(c.check())
        _say(f"reference {time.perf_counter() - t_ref:.3f} s")
    limits = spec["limits"]
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    checks["window_compiles"] = {"value": win["compiles"], "limit": 0}
    # the checked first chunk and the window's chunks: one executable
    checks["chunk_executables"] = {"value": win["chunk_executables"],
                                   "limit": 1}
    correct = (all(v["value"] <= v["limit"] for v in checks.values())
               and win["nonfinite_chunks"] == 0)
    result = {"correct": bool(correct), "attempted": win["chunks"],
              "failed": win["nonfinite_chunks"], "metrics": {},
              "device": device_info(devices, peak_bytes)}
    _say(f"window {win['seconds']:.3f} s, {win['chunks']} chunks, "
         f"{win['steps']} steps, input wait {win['input_wait_s']:.4f} s, "
         f"{nums['leaves_left_out']} leaves left out of the change")
    if not args.trace:
        e2e = {"train_member_steps_per_s": (
            c.real_members * win["steps"] / win["seconds"]),
            "setup_s": setup_s}
        for m in cell_metrics(bench, args.workload, "end_to_end"):
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    else:
        tr = Trace.from_file(str(next(pathlib.Path(tdir).rglob(
            "*.xplane.pb"))))
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx = {"trace": tr, "window": win, "chips": len(devices),
               "peak": peak, "layers": layer_patterns(bench_dir),
               "flops_per_step": flops.step_flops(
                   c.members, cfg["in_features"], cfg["classes"], c.batch)}
        for m in cell_metrics(bench, args.workload, "per_layer"):
            v = load_reader(bench_dir, m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        devs = sorted(tr.ops)
        result["device"]["busy_s"] = (sum(tr.busy_s(d) for d in devs)
                                      / max(len(devs), 1))
        result["device"]["window_s"] = tr.window_s
        result["breakdown"] = tr.breakdown()
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    # the TPU runtime logs to a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src")]
    try:
        import jax
        import repro  # noqa: F401 — the system under test
    except ImportError as e:
        _say(f"cannot import the system under test: {e}")
        return 2
    from repro.launch.cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = run(args)
    except SystemExit as e:
        _say(str(e))
        return 2
    for k, v in result["checks"].items():
        _say(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
