#!/usr/bin/env python3
"""Smoke run of the population engine on a TPU chip.

    python3 chip_smoke.py              # one chip: phases A-D
    python3 chip_smoke.py --chips 4    # four chips: the sharded phase only

Runs from the checkout root, in ONE process that touches JAX once, through
the entry points a user calls (``repro.launch.train.main`` and
``PopulationServer``); it starts no other process.  Weights are random,
drawn from ``--seed``; the data is the driver's seeded synthetic task.

One chip:
  A  the paper population (10,000 members, block 128, 100 features, 2
     classes) at batch 256 through the driver's default XLA path;
  B  the same population, seed and data through ``--bd-impl fused`` (the
     fused input kernel and the loss-head kernel);
  C  a deep heterogeneous population (1,024 members, all ten activations,
     block 128) through the fused mid-layer kernels with adamw;
  D  ``PopulationServer`` over B's parameters: publish, then best1 / topk /
     all request batches, in f32 and through the int8 serving copy.

``--chips 4``: only B's population trained with members sharded over the
'model' axis of ``make_host_mesh()``, compared with a one-device run of
the same steps in the same process; it also prints how many all-gathers
the compiled sharded chunk holds next to its Pallas kernels.

Each phase prints its compile seconds, first and last mean loss and
``TrainRunner`` restarts (the chip benchmark, ``chipbench/``, measures
speed).  Checks: the backend is ``tpu``;
every phase finishes with 0 restarts; losses are finite and fall; B's
per-member losses match A's within ``LOSS_TOL``; D's ensemble
probabilities match the XLA forward of the same parameters within
``PROB_TOL`` (the int8 copy against its dequantized f32 tree).  The
compared phases (A, B, D and the sharded phase) run at
``jax_default_matmul_precision = "highest"`` so each comparison holds f32
arithmetic on both sides: at the TPU's default precision both the XLA dots
and the Mosaic kernel dots round f32 operands to bf16, each in its own
order.  C, compared with nothing, runs at the default.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check exits non-zero; on a non-TPU backend the script refuses
before doing any work and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import tempfile
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# B vs A: per-member training loss after the same SGD steps, f32 against
# f32 (reassociation only — different reduction orders in the XLA einsum
# and the Pallas kernels)
LOSS_TOL = 1e-4
# D vs XLA forward: ensemble probabilities of the same parameters
PROB_TOL = 1e-4
# sharded vs one-device: the same kernels over the same member rows
SHARD_TOL = 1e-5

PAPER = ["--arch", "parallelmlp-10k", "--batch", "256", "--steps", "24",
         "--scan-steps", "8", "--ckpt-every", "0", "--seed", "0"]
DEEP = ["--arch", "parallelmlp-10k",
        "--population-depths", "512,256;256,128,64;384;128",
        "--population-repeats", "256", "--population-acts", "paper",
        "--population-block", "128", "--population-features", "100",
        "--optimizer", "adamw", "--bd-impl", "fused", "--batch", "256",
        "--steps", "24", "--scan-steps", "8", "--ckpt-every", "0",
        "--seed", "0"]
FUSED = ["--bd-impl", "fused"]
SERVE_BATCH = 32
SERVE_REQUESTS = 256
SERVE_CALIB = 512


def _say(msg: str):
    print(msg, flush=True)


def train_phase(name: str, argv, mesh=None) -> dict:
    """One driver run → its report (restarts, compile seconds,
    losses) plus the trained params and layout."""
    from repro.launch.train import main
    report = {}
    with tempfile.TemporaryDirectory() as ckpt:
        t0 = time.perf_counter()
        params, lp = main(list(argv) + ["--ckpt-dir", ckpt], report=report,
                          mesh=mesh)
        wall = time.perf_counter() - t0
    _say(f"[{name}] compile {report.get('compile_s', float('nan')):.2f} s; "
         f"mean loss {report.get('first_loss', float('nan')):.6f} -> "
         f"{report.get('last_loss', float('nan')):.6f}; "
         f"restarts {report.get('restarts')}; wall {wall:.1f} s")
    return {"params": params, "lp": lp, **report}


def train_failures(name: str, run: dict) -> list:
    import numpy as np
    out = []
    if run.get("restarts") != 0:
        out.append(f"{name}: {run.get('restarts')} restarts")
    first, last = run.get("first_loss"), run.get("last_loss")
    per = np.asarray(run.get("per_member_last", [np.nan]))
    if first is None or last is None or not (
            math.isfinite(first) and math.isfinite(last)
            and np.isfinite(per).all()):
        out.append(f"{name}: non-finite loss ({first} -> {last})")
    elif not last < first:
        out.append(f"{name}: loss did not fall ({first} -> {last})")
    return out


def member_diff(a: dict, b: dict) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a["per_member_last"])
                               - np.asarray(b["per_member_last"]))))


def serve_phase(params, lp, *, batch=SERVE_BATCH, n_req=SERVE_REQUESTS,
                n_calib=SERVE_CALIB, seed=0) -> list:
    """Publish + serve every ensemble mode in f32 and int8; each mode's
    probabilities are checked against the XLA forward of the parameters
    the server holds.  Returns failure strings."""
    import jax
    import numpy as np

    from repro.core import deep
    from repro.core.ensemble import ENSEMBLE_MODES, ensemble_predict
    from repro.data import TabularTask
    from repro.launch.serve_population import PopulationServer
    from repro.quant import dequantize_population

    task = TabularTask(n_calib + n_req, lp.in_features,
                       n_classes=lp.out_features, seed=seed)
    (xc, yc), (xr, _) = task.split(frac=n_calib / (n_calib + n_req))
    xr = np.asarray(xr[:n_req], np.float32)
    failures = []
    for wdt in (None, "int8"):
        tag = wdt or "f32"
        server = PopulationServer(params, lp, bd_impl="fused", batch=batch,
                                  topk=min(4, lp.num_real),
                                  weights_dtype=wdt)
        t0 = time.perf_counter()
        server.publish(xc, yc)
        _say(f"[D/{tag}] published in {time.perf_counter() - t0:.2f} s: "
             f"best1={server.published['best1']} "
             f"topk={server.published['topk']}")
        ref_params = (params if wdt is None
                      else dequantize_population(server.params, lp))
        logits = jax.jit(lambda p, x: deep.forward(p, x, lp))(ref_params, xr)
        for mode in ENSEMBLE_MODES:
            t0 = time.perf_counter()
            r = server.run(xr, mode)
            first = time.perf_counter() - t0 - r["wall_s"]
            ref = ensemble_predict(logits, lp, mode,
                                   member_ids=server.published.get(mode))
            ref_p = np.asarray(ref["probs"])
            diff = float(np.max(np.abs(r["probs"] - ref_p)))
            # predictions must agree wherever the top two classes are
            # further apart than the probability tolerance
            top2 = np.sort(ref_p, axis=1)[:, -2:]
            clear = top2[:, 1] - top2[:, 0] > 10 * PROB_TOL
            agree = bool(np.all(r["pred"][clear]
                                == np.asarray(ref["pred"])[clear]))
            _say(f"[D/{tag}] {mode:5s} members={r['members_served']} "
                 f"warm-up+compile {first:.2f} s; smoke p50 "
                 f"{r['p50_ms']:.3f} ms (not a benchmark); max |probs - "
                 f"xla| {diff:.3e}; preds agree {agree}")
            if not (diff <= PROB_TOL and agree):
                failures.append(f"D/{tag}/{mode}: probs differ by {diff} "
                                f"(tol {PROB_TOL}) or preds disagree")
    return failures


def count_collectives(hlo_text: str) -> dict:
    """All-gathers, all-reduces and Pallas kernels in a compiled module's
    text."""
    lines = hlo_text.splitlines()

    def n(*ops):
        return sum(1 for l in lines if any(f" {op}(" in l for op in ops))
    return {"all_gather": n("all-gather", "all-gather-start"),
            "all_reduce": n("all-reduce", "all-reduce-start"),
            "tpu_custom_call": sum(
                1 for l in lines
                if 'custom_call_target="tpu_custom_call"' in l)}


def sharded_chunk_text(lp, mesh, batch: int, scan: int) -> str:
    """Compiled text of the fused SGD chunk the driver builds for ``lp``
    on ``mesh`` — the program whose collectives the sharded phase counts."""
    import jax
    import jax.numpy as jnp

    from repro.core import deep
    from repro.distributed.sharding import (population_batch_shardings,
                                            population_opt_shardings,
                                            population_shardings)
    from repro.optim import sgd

    opt = sgd()
    chunk = deep.make_population_train_step(lp, optimizer=opt,
                                            bd_impl="fused",
                                            scan_steps=scan,
                                            donate_batch=True)
    params = deep.abstract_params(lp)
    st = jax.eval_shape(opt.init, params)
    sds = lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
    sh_x, sh_y = population_batch_shardings(mesh, batch)
    args = (jax.tree.map(sds, params, population_shardings(lp, mesh)),
            jax.tree.map(sds, st, population_opt_shardings(lp, opt, mesh)),
            jax.ShapeDtypeStruct((scan, batch, lp.in_features), jnp.float32,
                                 sharding=sh_x),
            jax.ShapeDtypeStruct((scan, batch), jnp.int32, sharding=sh_y),
            0.01)
    with jax.set_mesh(mesh):
        return chunk.lower(*args).compile().as_text()


def sharded_phase(argv) -> list:
    """B's population on every local chip (members over 'model') against
    a one-device run of the same steps."""
    import jax
    import numpy as np

    from repro.launch.mesh import make_host_mesh, make_mesh

    mesh = make_host_mesh()
    _say(f"[S] mesh {dict(mesh.shape)} over {len(jax.devices())} devices")
    failures = []
    with jax.default_matmul_precision("highest"):
        one = train_phase("S/1-device", argv, mesh=make_mesh(
            (1, 1), ("data", "model"), devices=jax.devices()[:1]))
        many = train_phase("S/sharded", argv, mesh=mesh)
    for name, run in (("S/1-device", one), ("S/sharded", many)):
        failures += train_failures(name, run)
    diff = member_diff(one, many)
    h = one["lp"].layer_pop(0).total_hidden
    w_diff = float(np.max(np.abs(
        np.asarray(one["params"]["w_in"][:h])
        - np.asarray(many["params"]["w_in"][:h]))))
    _say(f"[S] max per-member |loss sharded - 1-device| {diff:.3e} "
         f"(tol {SHARD_TOL}); max |w_in diff| {w_diff:.3e}")
    if not (diff <= SHARD_TOL and w_diff <= SHARD_TOL):
        failures.append(f"S: sharded run differs from one device "
                        f"(loss {diff}, w_in {w_diff})")
    scan = int(argv[argv.index("--scan-steps") + 1])
    batch = int(argv[argv.index("--batch") + 1])
    counts = count_collectives(sharded_chunk_text(many["lp"], mesh, batch,
                                                  scan))
    _say(f"[S] sharded fused chunk: {counts['tpu_custom_call']} "
         f"tpu_custom_call, {counts['all_gather']} all-gather, "
         f"{counts['all_reduce']} all-reduce")
    return failures


def single_chip(paper=PAPER, deep_argv=DEEP, fused=FUSED, serve_kw=None):
    """Phases A-D → failure strings."""
    import jax
    failures = []
    runs = {}
    with jax.default_matmul_precision("highest"):
        for name, argv in (("A", paper), ("B", list(paper) + list(fused))):
            try:
                runs[name] = train_phase(name, argv)
                failures += train_failures(name, runs[name])
            except Exception:  # noqa: BLE001 — report, then go on
                traceback.print_exc()
                failures.append(f"{name}: raised")
    if "A" in runs and "B" in runs:
        d = member_diff(runs["A"], runs["B"])
        _say(f"[B] max per-member |loss B - A| {d:.3e} (tol {LOSS_TOL})")
        if not d <= LOSS_TOL:
            failures.append(f"B: per-member loss differs from A by {d}")
    runs.pop("A", None)
    try:
        c = train_phase("C", deep_argv)
        failures += train_failures("C", c)
        del c
    except Exception:  # noqa: BLE001
        traceback.print_exc()
        failures.append("C: raised")
    if "B" in runs:
        try:
            with jax.default_matmul_precision("highest"):
                failures += serve_phase(runs["B"]["params"],
                                        runs["B"]["lp"], **(serve_kw or {}))
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append("D: raised")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)
    try:
        import jax
        import repro  # noqa: F401 — the checkout's package
    except ImportError as e:
        print(f"chip_smoke: cannot import the repository ({e}); run it "
              "from the checkout root", file=sys.stderr)
        return 2
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU backend, JAX found {backend!r}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    from repro.launch.cache import configure_compile_cache
    _say(f"compile cache: {configure_compile_cache()}")
    _say(f"devices: {len(devices)} x {devices[0].device_kind}")
    t0 = time.perf_counter()
    if args.chips == 4:
        try:
            failures = sharded_phase(PAPER + FUSED)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures = ["S: raised"]
    else:
        failures = single_chip()
    _say(f"total {time.perf_counter() - t0:.1f} s")
    for f in failures:
        print(f"FAILED {f}", file=sys.stderr)
    ok = not failures
    print(json.dumps({"ok": ok, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
