"""M3 implementation shoot-out (paper §5 "we believe M3 can be optimized").

Compares the four semantically-identical M3 implementations on the paper's
population layout:

  scatter   — paper-faithful broadcast-multiply + scatter-add (the GPU
              formulation; materialises the (B,O,H) intermediate)
  onehot    — dense einsum against a one-hot selector (P× redundant work)
  bucketed  — per-bucket batched matmul (best XLA-native TPU form)
  pallas    — segment-blocked matmul kernel (interpret mode on CPU)

Reports CPU wall-clock (fwd+bwd) AND the lowered dot-flops / HBM-byte
profile from the static HLO cost model — the structural numbers are what
transfer to TPU.

``--deep`` benches the layered-population engine instead: full fwd+bwd of a
mixed-depth LayeredPopulation with the block-diagonal mid layers run as the
per-bucket einsum loop vs the Pallas block_diag_gemm kernel (interpret mode
on CPU — wall-clock is NOT indicative there, the HLO structural numbers
are), and writes the rows to BENCH_deep.json so kernel perf is tracked
per-PR.

``--halving`` benches the successive-halving lifecycle (core.lifecycle):
the same step ladder trained with and without rung pruning + compaction,
wall-clock and final best-member loss to BENCH_halving.json — the tracked
number is the lifecycle's speedup at matched selection quality.

``--optim`` benches the stateful-optimizer engine (core.deep.opt_step /
make_population_train_step(optimizer=...)): the same scanned chunk under
sgd / momentum / adamw with f32 and bf16 moments, per-step wall-clock and
optimizer-state HBM overhead to BENCH_optim.json.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import LayeredPopulation, Population, init_params
from repro.core import deep as deep_mod
from repro.core.activations import PAPER_TEN
from repro.core.m3 import M3_IMPLS
from repro.launch.hlo_cost import analyze
from repro.launch.launch_count import fused_step_budget, phase_launches

try:                             # package import (python -m benchmarks.…)
    from benchmarks.roofline import kernel_roofline
except ImportError:              # flat import (CI scripts, tests)
    from roofline import kernel_roofline


def bench(pop, batch, impl, iters=5):
    params = init_params(jax.random.PRNGKey(0), pop)
    h = jax.random.normal(jax.random.PRNGKey(1), (batch, pop.total_hidden))
    w2 = params["w2"]
    fn = M3_IMPLS[impl]

    if impl == "pallas":
        def loss(hh, ww):
            return (fn(hh, ww, pop) ** 2).sum()
    else:
        def loss(hh, ww):
            return (fn(hh, ww, pop) ** 2).sum()

    step = jax.jit(jax.grad(loss, argnums=(0, 1)))
    out = step(h, w2)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(h, w2)
    jax.block_until_ready(out)
    wall = (time.perf_counter() - t0) / iters
    stats = analyze(jax.jit(loss).lower(h, w2).compile().as_text())
    return wall, stats


def _require_impl(bd_impl: str):
    """Fail LOUDLY when a requested mid-layer impl does not exist — a typo'd
    or backend-unavailable impl must abort the bench, not silently fall
    back and publish numbers for the wrong kernel."""
    if bd_impl not in deep_mod.BD_IMPLS:
        raise SystemExit(
            f"bd_impl {bd_impl!r} is not available on this backend; "
            f"registered impls: {sorted(deep_mod.BD_IMPLS)}")


def bench_deep(lp, batch, bd_impl, iters=3, shardings=None,
               act_impl="sliced", compute_dtype=None, reps=5):
    _require_impl(bd_impl)
    params = deep_mod.init_params(jax.random.PRNGKey(0), lp)
    if shardings is not None:
        params = jax.device_put(params, shardings)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, lp.in_features))
    y = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0,
                           lp.out_features)

    def loss(p):
        return deep_mod.fused_loss(p, x, y, lp, "bucketed", bd_impl,
                                   act_impl, compute_dtype)[0]

    step = jax.jit(jax.grad(loss))
    try:
        out = step(params)
        jax.block_until_ready(out)
    except Exception as e:
        raise RuntimeError(
            f"bd_impl {bd_impl!r} (act_impl={act_impl}, "
            f"compute_dtype={compute_dtype}) failed to compile/run on this "
            f"backend — refusing to fall back") from e
    walls = []
    for _ in range(reps):       # best-of-5: robust on contended CI hosts
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step(params)
        jax.block_until_ready(out)
        walls.append((time.perf_counter() - t0) / iters)
    wall = min(walls)
    # profile the SAME fwd+bwd computation the wall-clock measures, so the
    # tracked structural numbers catch backward-pass regressions too
    stats = analyze(step.lower(params).compile().as_text())
    return wall, stats


def bench_scan_vs_loop(lp, batch, scan_steps, steps=None, bd_impl="einsum",
                       shardings=None):
    """Per-step jitted dispatch loop vs ONE donated lax.scan chunk over the
    same optimizer steps (deep.make_population_train_step): the scanned
    chunk pays one dispatch per ``scan_steps`` steps and keeps params on
    device throughout."""
    steps = steps or scan_steps * 4
    steps -= steps % scan_steps
    params = deep_mod.init_params(jax.random.PRNGKey(0), lp)
    if shardings is not None:
        params = jax.device_put(params, shardings)
    xs = jax.random.normal(jax.random.PRNGKey(1),
                           (steps, batch, lp.in_features))
    ys = jax.random.randint(jax.random.PRNGKey(2), (steps, batch), 0,
                            lp.out_features)

    def run_loop(p):
        for i in range(steps):
            p, _, _ = deep_mod.sgd_step(p, xs[i], ys[i], 0.05, lp,
                                        "bucketed", bd_impl)
        return p

    def run_scan(p, chunk):
        for c in range(steps // scan_steps):
            sl = slice(c * scan_steps, (c + 1) * scan_steps)
            p, _, _ = chunk(p, xs[sl], ys[sl], 0.05)
        return p

    jax.block_until_ready(run_loop(jax.tree.map(jnp.copy, params)))  # compile
    t0 = time.perf_counter()
    jax.block_until_ready(run_loop(jax.tree.map(jnp.copy, params)))
    loop_s = time.perf_counter() - t0

    chunk = deep_mod.make_population_train_step(
        lp, bd_impl=bd_impl, scan_steps=scan_steps)
    jax.block_until_ready(run_scan(jax.tree.map(jnp.copy, params), chunk))
    t0 = time.perf_counter()
    jax.block_until_ready(run_scan(jax.tree.map(jnp.copy, params), chunk))
    scan_s = time.perf_counter() - t0

    return {"steps": steps, "scan_steps": scan_steps,
            "loop_ms_per_step": round(loop_s / steps * 1e3, 3),
            "scan_ms_per_step": round(scan_s / steps * 1e3, 3),
            "scan_speedup": round(loop_s / max(scan_s, 1e-12), 3)}


def _deep_bench_population(args):
    """The shared --deep/--fused bench population (mixed depths, the PR-1
    acceptance widths) and its optional host-mesh sharding — ONE builder so
    both modes always measure the same layout.  Returns
    (lp, mesh, shardings, mesh_ctx)."""
    import contextlib

    base = [(24,), (13, 5), (17, 9), (32, 16, 8)]
    lp = LayeredPopulation.grid(
        20, 2, base, ("relu", "tanh"),
        repeats=max(args.members // (2 * len(base)), 1), block=args.block)
    mesh = None
    shardings = None
    ctx = contextlib.nullcontext()
    if args.sharded:
        from jax import set_mesh
        from repro.distributed.sharding import (pop_axis_size,
                                                population_shardings)
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh()
        lp = lp.shard_pad(pop_axis_size(mesh))
        shardings = population_shardings(lp, mesh)
        ctx = set_mesh(mesh)
        print(f"# mesh: {dict(mesh.shape)} ({len(jax.devices())} devices)")
    print(f"# population: {lp.describe()}")
    return lp, mesh, shardings, ctx


def run_deep(args):
    """Mixed-depth layered population: einsum bucket loop vs the Pallas
    block-diagonal kernel (interpret on CPU), plus the scanned-chunk vs
    per-step-loop train-step shoot-out.  ``--sharded`` runs everything
    under the host mesh (population axis = 'model'; launch with
    XLA_FLAGS=--xla_force_host_platform_device_count=N to fake devices)."""
    lp, mesh, shardings, ctx = _deep_bench_population(args)

    with ctx:
        print("bd_impl,wall_ms,dot_gflops,hbm_mb")
        rows = {}
        for impl in args.bd_impls:
            wall, stats = bench_deep(lp, args.batch, impl,
                                     shardings=shardings)
            rows[impl] = {"wall_ms": round(wall * 1e3, 2),
                          "dot_gflops": round(stats["flops"] / 1e9, 4),
                          "hbm_mb": round(stats["hbm_bytes"] / 1e6, 2)}
            print(f"{impl},{wall*1e3:.2f},{stats['flops']/1e9:.3f},"
                  f"{stats['hbm_bytes']/1e6:.1f}", flush=True)
        train = bench_scan_vs_loop(lp, args.batch, args.scan_steps,
                                   shardings=shardings)
        print(f"# train step: loop {train['loop_ms_per_step']} ms/step vs "
              f"scan({train['scan_steps']}) {train['scan_ms_per_step']} "
              f"ms/step ({train['scan_speedup']}x)", flush=True)

    out = {"bench": "deep_population", "population": lp.describe(),
           "batch": args.batch, "results": rows, "train_step": train,
           "sharded": bool(args.sharded),
           "mesh": dict(mesh.shape) if mesh else None}
    if "einsum" in rows and "pallas" in rows:
        # the tracked pallas-vs-einsum HBM regression number (the kernel's
        # dense tile array reads vs the bucket loop's tight slices)
        out["hbm_gap_mb"] = round(rows["pallas"]["hbm_mb"]
                                  - rows["einsum"]["hbm_mb"], 2)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {args.json_out}")


def _phase_counts(lp, batch, impl, act, compute_dtype=None):
    """Static kernel-launch counts per phase for one fused-loss train step
    (repro.launch.launch_count): trace-only, so cheap at ANY batch size."""
    params = deep_mod.init_params(jax.random.PRNGKey(0), lp)
    x = jnp.zeros((batch, lp.in_features), jnp.float32)
    y = jnp.zeros((batch,), jnp.int32)

    def loss(p):
        return deep_mod.fused_loss(p, x, y, lp, "bucketed", impl, act,
                                   compute_dtype)[0]
    return phase_launches(loss, params)


def _check_budget(counts, budget, where):
    """The §9 launch-budget regression guard: the fully fused step must
    cost exactly 2·(depth+1) launches — MORE means a kernel stopped being
    one-pass (e.g. the backward re-grew a batch-size fallback), and the
    bench ABORTS rather than commit regressed numbers."""
    if counts["fwd"] > budget["fwd"] or counts["bwd"] > budget["bwd"]:
        raise SystemExit(
            f"kernel-launch budget EXCEEDED ({where}): counted {counts} "
            f"vs budget {budget} — the fused path is no longer one launch "
            "per layer per direction (DESIGN.md §9)")


def run_fused(args):
    """Fused-epilogue shoot-out (DESIGN.md §7/§9): the full fwd+bwd step
    of the layered engine, each mid-layer impl in its PRODUCTION config —

      einsum — per-bucket einsums + sliced XLA activations
      pallas — block-diag kernel + the seg_act round trip (GEMM writes
               pre-activations to HBM, seg_act reads them back — the path
               the fused kernel replaces)
      fused  — the one-pass-everywhere path: fused input layer, fused mid
               layers (projection + bias + activation per launch), fused
               loss head (projection + softmax-XE + dlogits) — no seg_act
               pass anywhere

    measured at f32 AND bf16 operands (the --compute-dtype policy), wall,
    loop-aware HLO HBM, per-phase KERNEL-LAUNCH counts, and achieved
    roofline coordinates side by side → BENCH_fused.json.  The fused rows
    are checked against the §9 budget (2·(depth+1) launches per step,
    batch-independent) and a batch sweep (32/256/1024) proves the
    independence in the committed artifact.  A requested impl that is
    missing or fails on this backend ABORTS the bench (no silent
    fallback), as does a budget overrun."""
    lp, mesh, shardings, ctx = _deep_bench_population(args)

    act_for = {"einsum": "sliced", "pallas": "pallas", "fused": "pallas"}
    impls = args.bd_impls or ["einsum", "pallas", "fused"]
    for impl in impls:
        _require_impl(impl)
    budget = fused_step_budget(lp.depth)
    rows = {}
    with ctx:
        print("bd_impl,dtype,act_impl,wall_ms,hbm_mb,launches")
        for impl in impls:
            act = act_for.get(impl, "sliced")
            counts = _phase_counts(lp, args.batch, impl, act)
            if impl == "fused":
                _check_budget(counts, budget, f"impl=fused B={args.batch}")
            rows[impl] = {"act_impl": act, "kernel_launches": counts}
            for dt in ("float32", "bfloat16"):
                wall, stats = bench_deep(
                    lp, args.batch, impl, shardings=shardings,
                    act_impl=act, compute_dtype=dt)
                rows[impl][dt] = {
                    "wall_ms": round(wall * 1e3, 2),
                    "hbm_mb": round(stats["hbm_bytes"] / 1e6, 2),
                    "roofline": kernel_roofline(stats["flops"],
                                                stats["hbm_bytes"], wall)}
                print(f"{impl},{dt},{act},{wall*1e3:.2f},"
                      f"{stats['hbm_bytes']/1e6:.1f},{counts['total']}",
                      flush=True)

        # ---- batch sweep: the §9 invariant made CONCRETE — the fused
        # step's launch count must not move with B (the two-level-grid
        # backward is what removed the batch fallback), while wall/HBM
        # scale.  Large-B wall-clock is measured at reduced reps (CPU
        # interpret mode is slow there; the launch counts are the tracked
        # regression numbers, the wall is context).
        sweep = {}
        for bsz in args.sweep_batches:
            counts = _phase_counts(lp, bsz, "fused", act_for["fused"])
            _check_budget(counts, budget, f"sweep B={bsz}")
            row = {"kernel_launches": counts}
            if not args.sweep_launches_only:
                light = bsz > args.batch
                wall, stats = bench_deep(
                    lp, bsz, "fused", shardings=shardings,
                    act_impl=act_for["fused"], compute_dtype="float32",
                    iters=1 if light else 3, reps=2 if light else 5)
                row.update({
                    "wall_ms": round(wall * 1e3, 2),
                    "hbm_mb": round(stats["hbm_bytes"] / 1e6, 2),
                    "roofline": kernel_roofline(stats["flops"],
                                                stats["hbm_bytes"], wall)})
                print(f"# sweep B={bsz}: {row['wall_ms']} ms, "
                      f"{row['hbm_mb']} MB, launches {counts}", flush=True)
            else:
                print(f"# sweep B={bsz}: launches {counts}", flush=True)
            sweep[str(bsz)] = row
        launch_sets = {json.dumps(r["kernel_launches"], sort_keys=True)
                       for r in sweep.values()}
        if len(launch_sets) > 1:
            raise SystemExit(
                f"fused launch count varies with batch size: {sweep} — "
                "the one-pass backward regressed to a batch-dependent grid")

    out = {"bench": "fused_layer", "population": lp.describe(),
           "batch": args.batch, "results": rows,
           "launch_budget": budget, "batch_sweep": sweep,
           "sharded": bool(args.sharded),
           "mesh": dict(mesh.shape) if mesh else None}
    if "fused" in rows and "pallas" in rows:
        pw, fw = (rows[i]["float32"] for i in ("pallas", "fused"))
        out["headline"] = {
            "fused_vs_pallas_speedup": round(
                pw["wall_ms"] / max(fw["wall_ms"], 1e-9), 3),
            "fused_vs_pallas_hbm_delta_mb": round(
                fw["hbm_mb"] - pw["hbm_mb"], 2)}
        bf = rows["fused"].get("bfloat16")
        if bf:
            out["headline"]["fused_bf16_hbm_mb"] = bf["hbm_mb"]
        if args.sharded and args.members == 8 and args.batch == 32:
            # the tracked regression anchor: bd_impl=pallas on these exact
            # shapes as committed by PR 3 (BENCH_deep_sharded.json, dense
            # (out_tiles × k_max) grid, act sliced) — what the fused kernel
            # + ragged-grid fix set out to beat
            out["baseline_pr3_pallas"] = {
                "wall_ms": 188.2, "hbm_mb": 65.79,
                "source": "BENCH_deep_sharded.json @ PR 3",
                "fused_speedup": round(188.2 / max(fw["wall_ms"], 1e-9), 3),
                "fused_hbm_delta_mb": round(fw["hbm_mb"] - 65.79, 2)}
            print(f"# fused vs PR-3 pallas baseline: "
                  f"{out['baseline_pr3_pallas']['fused_speedup']}x wall, "
                  f"{out['baseline_pr3_pallas']['fused_hbm_delta_mb']:+.1f}"
                  " MB HBM", flush=True)
        print(f"# fused vs pallas (this run): "
              f"{out['headline']['fused_vs_pallas_speedup']}x wall, "
              f"{out['headline']['fused_vs_pallas_hbm_delta_mb']:+.1f} MB "
              "HBM", flush=True)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {args.json_out}")
    return out


def run_serve(args):
    """Forward-only serving bench (DESIGN.md §10) → BENCH_serve.json.
    Three proofs in one artifact:

      1. LAUNCH BUDGET — ``forward(infer=True)`` traces to exactly depth+1
         Pallas launches, every one single-output (no residual buffer can
         exist in the program).  Overrun or a 2-output launch ABORTS.
      2. FORWARD-ONLY vs TRAINING-FORWARD REUSE — the infer path against
         what serving without it would run: the training step's VJP-forward
         (``jax.vjp(forward)[0]``), whose kernels emit g' residuals that
         stay live because the jaxpr cannot drop one output of a used
         pallas_call.  The infer path must be STRICTLY better on wall AND
         HBM (ABORT otherwise); the HBM delta is the residual footprint,
         verifiably gone.
      3. SERVING ENGINE — p50/p99 latency + req/s vs ensemble size
         (all / top-k / best-1) through ``PopulationServer``'s batching
         loop, member set published from a calibration leaderboard."""
    from repro.core.ensemble import real_slots
    from repro.data.synthetic import TabularTask
    from repro.launch.launch_count import (count_pallas_launches,
                                           fused_infer_budget,
                                           max_eqn_outputs)
    from repro.launch.serve_population import PopulationServer

    _require_impl("fused")
    lp, mesh, shardings, ctx = _deep_bench_population(args)
    params = deep_mod.init_params(jax.random.PRNGKey(0), lp)
    if shardings is not None:
        params = jax.device_put(params, shardings)
    budget = fused_infer_budget(lp.depth)
    # the forward proof runs at its own batch: residual buffers scale with
    # B (g' is (B, H_out) per layer), so the honest comparison point is a
    # serving-slab batch where reuse actually pays for them — at tiny B
    # both programs are noise-sized and the delta is unmeasurable
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (args.fwd_batch, lp.in_features))

    def infer_fwd(p):
        return deep_mod.forward(p, x, lp, bd_impl="fused",
                                act_impl="pallas", infer=True)

    def train_reuse_fwd(p):
        # serving off the training step's forward: the VJP-forward keeps
        # every kernel's residual output alive alongside the logits
        return jax.vjp(lambda q: deep_mod.forward(
            q, x, lp, bd_impl="fused", act_impl="pallas"), p)[0]

    with ctx:
        got = count_pallas_launches(infer_fwd, params)
        if got != budget["total"]:
            raise SystemExit(
                f"infer launch budget EXCEEDED: counted {got} vs "
                f"{budget['total']} (= depth+1, DESIGN.md §10)")
        worst = max_eqn_outputs(infer_fwd, params)
        if worst > 1:
            raise SystemExit(
                f"infer forward emits a {worst}-output pallas_call — a "
                "residual buffer survived in the serving program")
        reuse_worst = max_eqn_outputs(train_reuse_fwd, params)
        print(f"# infer launches {got} (budget {budget['total']}); "
              f"max pallas outputs: infer {worst}, train-reuse "
              f"{reuse_worst}", flush=True)

        def best_of(fn, iters=3, reps=5):
            f = jax.jit(fn)
            jax.block_until_ready(f(params))
            walls = []
            for _ in range(reps):
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = f(params)
                jax.block_until_ready(out)
                walls.append((time.perf_counter() - t0) / iters)
            stats = analyze(f.lower(params).compile().as_text())
            return min(walls), stats

        i_wall, i_stats = best_of(infer_fwd)
        r_wall, r_stats = best_of(train_reuse_fwd)
        fwd_cmp = {
            "infer": {"wall_ms": round(i_wall * 1e3, 2),
                      "hbm_mb": round(i_stats["hbm_bytes"] / 1e6, 2)},
            "train_reuse": {"wall_ms": round(r_wall * 1e3, 2),
                            "hbm_mb": round(r_stats["hbm_bytes"] / 1e6, 2)},
            "speedup": round(r_wall / max(i_wall, 1e-12), 3),
            "residual_hbm_mb": round(
                (r_stats["hbm_bytes"] - i_stats["hbm_bytes"]) / 1e6, 2),
        }
        print(f"# forward-only {fwd_cmp['infer']['wall_ms']} ms / "
              f"{fwd_cmp['infer']['hbm_mb']} MB vs train-reuse "
              f"{fwd_cmp['train_reuse']['wall_ms']} ms / "
              f"{fwd_cmp['train_reuse']['hbm_mb']} MB "
              f"({fwd_cmp['speedup']}x, residuals "
              f"{fwd_cmp['residual_hbm_mb']} MB)", flush=True)
        if i_wall >= r_wall or i_stats["hbm_bytes"] >= r_stats["hbm_bytes"]:
            raise SystemExit(
                "forward-only path does NOT strictly beat training-forward "
                f"reuse: {fwd_cmp} — the §10 residual-free contract "
                "regressed")

        # ---- serving engine: latency/throughput vs ensemble size
        server = PopulationServer(
            params, lp, mesh=mesh, batch=args.batch, topk=args.topk,
            max_latency_ms=args.max_latency_ms)
        task = TabularTask(512 + args.serve_requests, lp.in_features,
                           n_classes=lp.out_features, seed=0)
        (xc, yc), (xr, _) = task.split(
            frac=512 / (512 + args.serve_requests))
        board = server.publish(xc, yc)
        serve_rows = {}
        print("mode,members,p50_ms,p99_ms,req_per_s")
        for mode in ("all", "topk", "best1"):
            r = server.run(xr[:args.serve_requests], mode)
            serve_rows[mode] = {
                "members_served": r["members_served"],
                "requests": r["requests"],
                "p50_ms": round(r["p50_ms"], 3),
                "p99_ms": round(r["p99_ms"], 3),
                "req_per_s": round(r["req_per_s"], 1)}
            print(f"{mode},{r['members_served']},{r['p50_ms']:.2f},"
                  f"{r['p99_ms']:.2f},{r['req_per_s']:.0f}", flush=True)

    out = {"bench": "serve", "population": lp.describe(),
           "batch": args.batch, "fwd_batch": args.fwd_batch,
           "topk": args.topk,
           "max_latency_ms": args.max_latency_ms,
           "members": real_slots(lp),
           "launch_budget": {**budget, "counted": got,
                             "max_pallas_outputs": worst,
                             "train_reuse_max_outputs": reuse_worst},
           "forward_only_vs_train_reuse": fwd_cmp,
           "serve": serve_rows,
           "board_top3": board[:3],
           "sharded": bool(args.sharded),
           "mesh": dict(mesh.shape) if mesh else None}
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2, default=str)
        print(f"# wrote {args.json_out}")
    return out


def run_quant(args):
    """Int8 weight-only serving bench (DESIGN.md §12) → BENCH_quant.json.
    Three proofs in one artifact:

      1. LAUNCH BUDGET — ``forward(infer=True, weights_dtype="int8")``
         traces to exactly depth+1 Pallas launches, every one
         single-output: fusing the dequant into the tile loops must not
         cost a launch or re-open the residual hole.  Overrun ABORTS.
      2. WEIGHT-STORE CONTEST at ``--fwd-batch`` — the int8 serve copy
         (pre-packed tiles, dequant on the VPU inside the tile loop, f32
         weights never materialised) against the bf16 half-width store at
         EQUAL activation precision: a bf16 store feeding f32-activation
         kernels must upcast every weight leaf per flush and re-pack the
         block-diagonal tiles per call.  int8 must be STRICTLY better on
         wall-clock AND loop-aware HLO HBM (ABORT otherwise).  The f32
         committed serve path rides along informationally.  NOT measured
         here: ``compute_dtype="bfloat16"`` (bf16 ACTIVATIONS) — that
         trades accuracy for activation bytes and is orthogonal to the
         weight store.
      3. ACCURACY GATE — a briefly-trained population's calibration-split
         accuracy under int8 vs f32, per ensemble mode (all / topk /
         best1, same published member set).  |delta| > 0.5% absolute on
         any mode ABORTS — the 4x weight-HBM saving is only committed
         when it is numerically free at serving granularity."""
    from repro.core.ensemble import ensemble_predict, real_slots
    from repro.core.selection import evaluate_population, leaderboard
    from repro.data.synthetic import TabularTask
    from repro.launch.launch_count import (count_pallas_launches,
                                           fused_infer_budget,
                                           max_eqn_outputs)
    from repro.quant import quantize_population, serve_copy_bytes

    _require_impl("fused")
    lp, mesh, shardings, ctx = _deep_bench_population(args)
    budget = fused_infer_budget(lp.depth)

    with ctx:
        params = deep_mod.init_params(jax.random.PRNGKey(0), lp)
        if shardings is not None:
            params = jax.device_put(params, shardings)

        # brief training so the accuracy gate scores real decision margins
        # (an untrained net's logit margins cluster at zero, where ANY
        # perturbation flips predictions — the gate would measure noise)
        ncal = args.quant_calib
        task = TabularTask(max(4096, 2 * ncal), lp.in_features,
                           n_classes=lp.out_features, seed=0)
        (xtr, ytr), (xc, yc) = task.split(frac=0.5)
        xc, yc = np.asarray(xc[:ncal]), np.asarray(yc[:ncal])
        steps = args.quant_train_steps
        if steps:
            rng = np.random.default_rng(0)
            idx = rng.integers(0, xtr.shape[0], size=(steps, args.batch))
            chunk = deep_mod.make_population_train_step(
                lp, scan_steps=steps, donate=False)
            params = jax.block_until_ready(chunk(
                params, jnp.asarray(np.asarray(xtr)[idx]),
                jnp.asarray(np.asarray(ytr)[idx]), 0.05))[0]

        # the three weight stores: f32 masters (committed serve path /
        # accuracy reference), bf16 half-width store (strict-win baseline),
        # int8 serve copy (packed + augmented + padded at quantize time)
        qparams = jax.block_until_ready(
            jax.jit(quantize_population, static_argnums=1)(params, lp))
        bf16_params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16), params)
        copy_mb = {
            "f32": round(serve_copy_bytes(params) / 1e6, 3),
            "bf16": round(serve_copy_bytes(bf16_params) / 1e6, 3),
            "int8": round(serve_copy_bytes(qparams) / 1e6, 3),
        }
        copy_mb["int8_vs_f32"] = round(copy_mb["f32"] / copy_mb["int8"], 2)
        print(f"# serve copy: f32 {copy_mb['f32']} MB, bf16 "
              f"{copy_mb['bf16']} MB, int8 {copy_mb['int8']} MB "
              f"({copy_mb['int8_vs_f32']}x vs f32)", flush=True)

        x = jax.random.normal(jax.random.PRNGKey(1),
                              (args.fwd_batch, lp.in_features))

        def f32_fwd(p):
            return deep_mod.forward(p, x, lp, bd_impl="fused",
                                    act_impl="pallas", infer=True)

        def bf16_fwd(p):
            # serving off a bf16 weight store at f32 activation precision:
            # every weight leaf upcasts per flush, then the forward re-packs
            # the block-diagonal tiles per call like the f32 path
            pf = jax.tree.map(lambda a: a.astype(jnp.float32), p)
            return deep_mod.forward(pf, x, lp, bd_impl="fused",
                                    act_impl="pallas", infer=True)

        def int8_fwd(p):
            return deep_mod.forward(p, x, lp, bd_impl="fused",
                                    act_impl="pallas", infer=True,
                                    weights_dtype="int8")

        got = count_pallas_launches(int8_fwd, qparams)
        if got != budget["total"]:
            raise SystemExit(
                f"int8 infer launch budget EXCEEDED: counted {got} vs "
                f"{budget['total']} (= depth+1, DESIGN.md §10/§12)")
        worst = max_eqn_outputs(int8_fwd, qparams)
        if worst > 1:
            raise SystemExit(
                f"int8 infer forward emits a {worst}-output pallas_call — "
                "a residual buffer survived in the quantized serving "
                "program")
        print(f"# int8 infer launches {got} (budget {budget['total']}); "
              f"max pallas outputs {worst}", flush=True)

        def best_of(fn, p, iters=3, reps=5):
            f = jax.jit(fn)
            jax.block_until_ready(f(p))
            walls = []
            for _ in range(reps):
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = f(p)
                jax.block_until_ready(out)
                walls.append((time.perf_counter() - t0) / iters)
            stats = analyze(f.lower(p).compile().as_text())
            return min(walls), stats

        rows = {}
        print("weights,wall_ms,hbm_mb")
        for name, fn, p in (("f32", f32_fwd, params),
                            ("bf16", bf16_fwd, bf16_params),
                            ("int8", int8_fwd, qparams)):
            wall, stats = best_of(fn, p)
            rows[name] = {"wall_ms": round(wall * 1e3, 3),
                          "hbm_mb": round(stats["hbm_bytes"] / 1e6, 3),
                          "_wall": wall, "_hbm": stats["hbm_bytes"]}
            print(f"{name},{wall*1e3:.2f},{stats['hbm_bytes']/1e6:.2f}",
                  flush=True)
        q, b = rows["int8"], rows["bf16"]
        fwd_cmp = {
            k: {kk: vv for kk, vv in v.items() if not kk.startswith("_")}
            for k, v in rows.items()}
        fwd_cmp["int8_vs_bf16_speedup"] = round(b["_wall"]
                                                / max(q["_wall"], 1e-12), 3)
        fwd_cmp["int8_vs_bf16_hbm_saving_mb"] = round(
            (b["_hbm"] - q["_hbm"]) / 1e6, 3)
        print(f"# int8 vs bf16: {fwd_cmp['int8_vs_bf16_speedup']}x wall, "
              f"{fwd_cmp['int8_vs_bf16_hbm_saving_mb']:+.2f} MB HBM",
              flush=True)
        if q["_wall"] >= b["_wall"] or q["_hbm"] >= b["_hbm"]:
            raise SystemExit(
                "int8 serve copy does NOT strictly beat the bf16 weight "
                f"store: {fwd_cmp} — refusing to commit a no-win artifact "
                "(DESIGN.md §12)")

        # ---- accuracy gate: per-mode calibration accuracy, f32 vs int8,
        # over the SAME published member set (ranked on the f32 masters so
        # the delta isolates quantization, not re-ranking)
        losses, accs = evaluate_population(
            params, lp, jnp.asarray(xc), jnp.asarray(yc),
            bd_impl="fused", act_impl="pallas", infer=True)
        board = leaderboard(lp, losses, accs, k=max(args.topk, 1))
        published = {"all": None,
                     "topk": [r["slot"] for r in board[:args.topk]],
                     "best1": [board[0]["slot"]]}
        lg_f = jax.jit(lambda p, xb: deep_mod.forward(
            p, xb, lp, bd_impl="fused", act_impl="pallas",
            infer=True))(params, jnp.asarray(xc))
        lg_q = jax.jit(lambda p, xb: deep_mod.forward(
            p, xb, lp, bd_impl="fused", act_impl="pallas", infer=True,
            weights_dtype="int8"))(qparams, jnp.asarray(xc))

        calib = {}
        print("mode,f32_acc,int8_acc,delta")
        for mode in ("all", "topk", "best1"):
            ids = published[mode]
            a_f = float((np.asarray(ensemble_predict(
                lg_f, lp, mode, member_ids=ids)["pred"]) == yc).mean())
            a_q = float((np.asarray(ensemble_predict(
                lg_q, lp, mode, member_ids=ids)["pred"]) == yc).mean())
            calib[mode] = {"f32_acc": round(a_f, 5),
                           "int8_acc": round(a_q, 5),
                           "delta": round(a_q - a_f, 5)}
            print(f"{mode},{a_f:.4f},{a_q:.4f},{a_q - a_f:+.4f}",
                  flush=True)
            if abs(a_q - a_f) > 0.005:
                raise SystemExit(
                    f"int8 calibration accuracy delta {a_q - a_f:+.4f} on "
                    f"mode {mode!r} exceeds the 0.5% bound — the serve "
                    "copy is NOT numerically free (DESIGN.md §12)")

    out = {"bench": "quant_serve", "population": lp.describe(),
           "fwd_batch": args.fwd_batch, "topk": args.topk,
           "members": real_slots(lp),
           "calib_samples": ncal, "train_steps": steps,
           "launch_budget": {**budget, "counted": got,
                             "max_pallas_outputs": worst},
           "serve_copy_mb": copy_mb,
           "forward": fwd_cmp,
           "calibration": calib,
           "sharded": bool(args.sharded),
           "mesh": dict(mesh.shape) if mesh else None,
           "note": "bf16 = bf16 WEIGHT STORE at f32 activation precision "
                   "(upcast per flush + per-call tile packing) — the "
                   "honest weight-only baseline; compute_dtype='bfloat16' "
                   "(bf16 activations) is an orthogonal accuracy/HBM "
                   "trade and not this contest. int8 consumes the "
                   "pre-packed, pre-augmented quantize_population copy "
                   "with dequant fused into the tile loops. Accuracy "
                   "deltas are over the same f32-ranked member set"}
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2, default=str)
        print(f"# wrote {args.json_out}")
    return out


def _tree_mb(abs_tree) -> float:
    """Static HBM residency of an abstract tree (ShapeDtypeStructs), MB."""
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree.leaves(abs_tree)) / 1e6


def run_optim(args):
    """Stateful-optimizer shoot-out (DESIGN.md §8): the SAME scanned
    population train chunk driven by sgd / momentum / adamw (f32 and bf16
    moments side by side), reporting AOT-compiled per-step wall-clock, the
    loop-aware HLO HBM profile, and the optimizer-state HBM overhead
    (state bytes vs param bytes — the number that decides whether a 10k-
    member population's moments fit next to its params) →
    BENCH_optim.json.  The stateless legacy chunk rides along as the
    engine-overhead baseline: plain sgd through the engine must cost the
    same wall-clock (and is bit-exact, tests/test_population_optim.py)."""
    from repro.optim import adamw, sgd

    lp, mesh, shardings, ctx = _deep_bench_population(args)
    configs = [
        ("sgd", sgd()),
        ("momentum", sgd(momentum=0.9)),
        ("adamw", adamw(weight_decay=0.0)),
        ("adamw_bf16m", adamw(weight_decay=0.0,
                              state_dtype=jnp.bfloat16)),
    ]
    steps = args.scan_steps
    abs_p = deep_mod.abstract_params(lp)
    params_mb = _tree_mb(abs_p)
    params = deep_mod.init_params(jax.random.PRNGKey(0), lp)
    if shardings is not None:
        params = jax.device_put(params, shardings)
    xs = jax.random.normal(jax.random.PRNGKey(1),
                           (steps, args.batch, lp.in_features))
    ys = jax.random.randint(jax.random.PRNGKey(2), (steps, args.batch), 0,
                            lp.out_features)

    def best_of(fn, *a, iters=3):
        # best-of-5 × iters chunk calls per sample: the bench_deep
        # convention, robust on contended CI hosts
        jax.block_until_ready(fn(*a))
        walls = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(*a)
            jax.block_until_ready(out)
            walls.append((time.perf_counter() - t0) / iters)
        return min(walls) / steps * 1e3        # ms per optimizer step

    rows = {}
    with ctx:
        legacy = deep_mod.make_population_train_step(
            lp, scan_steps=steps, donate=False)
        legacy_c = legacy.lower(params, xs, ys, 0.05).compile()
        legacy_ms = best_of(legacy_c, params, xs, ys, 0.05)
        print(f"# stateless legacy chunk: {legacy_ms:.2f} ms/step")
        print("optimizer,step_ms,opt_state_mb,opt_overhead,hbm_mb")
        for name, opt in configs:
            chunk = deep_mod.make_population_train_step(
                lp, optimizer=opt, scan_steps=steps, donate=False)
            st = opt.init(params)
            compiled = chunk.lower(params, st, xs, ys, 0.05).compile()
            step_ms = best_of(compiled, params, st, xs, ys, 0.05)
            opt_mb = _tree_mb(jax.eval_shape(opt.init, abs_p))
            stats = analyze(compiled.as_text())
            rows[name] = {
                "step_ms": round(step_ms, 3),
                "opt_state_mb": round(opt_mb, 3),
                "opt_overhead": round(opt_mb / params_mb, 3),
                "hbm_mb": round(stats["hbm_bytes"] / 1e6, 2),
            }
            print(f"{name},{step_ms:.2f},{opt_mb:.3f},"
                  f"{opt_mb / params_mb:.3f},"
                  f"{stats['hbm_bytes']/1e6:.1f}", flush=True)

    out = {"bench": "population_optimizers", "population": lp.describe(),
           "batch": args.batch, "scan_steps": steps,
           "params_mb": round(params_mb, 3),
           "legacy_sgd_step_ms": round(legacy_ms, 3),
           "results": rows,
           "sharded": bool(args.sharded),
           "mesh": dict(mesh.shape) if mesh else None,
           "note": "CPU wall-clock is noise-bound at these shapes (same "
                   "caveat as the --deep bench); the TRACKED numbers are "
                   "the structural ones — opt_state_mb, opt_overhead "
                   "(state/params bytes) and the HLO hbm_mb profile"}
    out["headline"] = {
        # engine overhead of plain sgd vs the stateless chunk (≈1.0: the
        # engine is free where it changes nothing)
        "engine_vs_legacy": round(
            rows["sgd"]["step_ms"] / max(legacy_ms, 1e-9), 3),
        # what bf16 moments buy back (the §8 state-dtype policy)
        "adamw_bf16_state_saving_mb": round(
            rows["adamw"]["opt_state_mb"]
            - rows["adamw_bf16m"]["opt_state_mb"], 3)}
    print(f"# engine vs legacy: {out['headline']['engine_vs_legacy']}x; "
          f"adamw bf16 moments save "
          f"{out['headline']['adamw_bf16_state_saving_mb']} MB "
          f"(params {params_mb:.2f} MB)", flush=True)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {args.json_out}")
    return out


def run_halving(args):
    """Successive-halving lifecycle vs full-population training on the SAME
    ladder of global steps (core.lifecycle; DESIGN.md §6): both runs train
    to ``--halving-steps``, the halving run additionally prunes + compacts
    at each rung, so later segments train a physically smaller fused
    layout.  Reports train-execution wall-clock (chunks are AOT-compiled
    first; compile time and the rung evals are EXCLUDED — the structural
    ``member_steps`` ratio is reported alongside so the wall-clock speedup
    can be sanity-checked), plus the final best-member validation loss of
    each run, to BENCH_halving.json."""
    from repro.core import lifecycle
    from repro.core.selection import evaluate_population
    from repro.data import TabularTask

    base = [(48, 24), (64, 32), (40, 16), (56, 28)]
    lp0 = LayeredPopulation.grid(
        20, 2, base, ("relu", "tanh"),
        repeats=max(args.members // (2 * len(base)), 1), block=args.block)
    schedule = lifecycle.HalvingSchedule.parse(args.halving)
    total = args.halving_steps
    task = TabularTask(4096, 20, n_classes=2, seed=0)
    _, (xte, yte) = task.split()
    xte, yte = jnp.asarray(xte), jnp.asarray(yte)

    def batches(a, b):
        bs = [task.batch(s, args.batch) for s in range(a, b)]
        return (jnp.asarray(np.stack([x for x, _ in bs])),
                jnp.asarray(np.stack([y for _, y in bs])))

    def run(segments):
        lp = lp0
        params = deep_mod.init_params(jax.random.PRNGKey(0), lp)
        wall = eval_s = 0.0
        member_steps = 0
        pos = 0
        rung_evals = []
        n_rung = xte.shape[0]
        if args.rung_eval_batches:
            # cheap rungs: rank fidelity at the cut line only needs a
            # subsample; the FINAL selection eval below stays full-split
            n_rung = min(n_rung, args.rung_eval_batches * args.batch)
        for (end, frac) in segments:
            # one scan chunk per segment, AOT-compiled out of the timing
            chunk = deep_mod.make_population_train_step(
                lp, scan_steps=end - pos, donate=False)
            xs, ys = batches(pos, end)
            compiled = chunk.lower(params, xs, ys, 0.05).compile()
            t0 = time.perf_counter()
            out = compiled(params, xs, ys, 0.05)
            jax.block_until_ready(out)
            wall += time.perf_counter() - t0
            params = out[0]
            member_steps += lp.num_members * (end - pos)
            pos = end
            if frac is not None:
                # warm the per-layout eval jit, then time steady state —
                # the same compile-excluded convention as the train chunks
                evaluate_population(params, lp, xte[:n_rung], yte[:n_rung])
                t0 = time.perf_counter()
                losses, _ = evaluate_population(params, lp, xte[:n_rung],
                                                yte[:n_rung])
                keep = lifecycle.survivors(np.asarray(losses), frac)
                dt_eval = time.perf_counter() - t0
                # warm the (lru-cached) device-gather jit out of the
                # timing — the same compile-excluded convention as the
                # train chunks and the rung evals
                lifecycle.compact(lp, params, None, keep)
                t1 = time.perf_counter()
                lp, params, _ = lifecycle.compact(lp, params, None, keep)
                # the device-gathered tree re-materialises as part of the
                # prune overhead, not the next segment's train wall-clock
                params = jax.block_until_ready(
                    jax.tree.map(jnp.asarray, params))
                dt_rung = dt_eval + (time.perf_counter() - t1)
                eval_s += dt_rung
                rung_evals.append({"step": end, "eval_s": round(dt_eval, 4),
                                   "prune_s": round(dt_rung - dt_eval, 4),
                                   "samples": int(n_rung)})
                print(f"# rung @ {end}: kept {len(keep)} members "
                      f"(eval {dt_eval*1e3:.0f} ms on {n_rung} samples; "
                      f"fused hidden "
                      f"{[lp.layer_pop(l).total_hidden for l in range(lp.depth)]})",
                      flush=True)
        losses, _ = evaluate_population(params, lp, xte, yte)
        return (wall, eval_s, member_steps,
                float(np.min(np.asarray(losses))), rung_evals)

    print(f"# population: {lp0.describe()}")
    print(f"# ladder: {schedule.rungs} over {total} steps")
    full_wall, _, full_ms, full_best, _ = run(((total, None),))
    halv_wall, halv_eval, halv_ms, halv_best, rung_evals = run(
        schedule.segments(total))
    out = {
        "bench": "halving_lifecycle", "population": lp0.describe(),
        "batch": args.batch, "steps": total,
        "ladder": [list(r) for r in schedule.rungs],
        "rung_eval_batches": args.rung_eval_batches,
        "full": {"wall_s": round(full_wall, 3), "member_steps": full_ms,
                 "best_loss": round(full_best, 5)},
        "halving": {"wall_s": round(halv_wall, 3), "member_steps": halv_ms,
                    "best_loss": round(halv_best, 5),
                    "prune_overhead_s": round(halv_eval, 3),
                    "rung_evals": rung_evals},
        "speedup": round(full_wall / max(halv_wall, 1e-12), 3),
        "speedup_end_to_end": round(
            full_wall / max(halv_wall + halv_eval, 1e-12), 3),
        "member_step_ratio": round(full_ms / halv_ms, 3),
        "best_loss_gap": round(halv_best - full_best, 5),
        "note": "compile-excluded wall-clock throughout: wall_s is "
                "AOT-compiled train-chunk execution, prune_overhead_s is "
                "steady-state rung eval + host compaction and counts "
                "against speedup_end_to_end",
    }
    print(f"# full: {full_wall:.2f}s ({full_ms} member-steps), "
          f"best loss {full_best:.4f}")
    print(f"# halving: {halv_wall:.2f}s train + {halv_eval:.2f}s prune "
          f"({halv_ms} member-steps), best loss {halv_best:.4f} -> "
          f"{out['speedup']}x train / {out['speedup_end_to_end']}x "
          f"end-to-end, loss gap {out['best_loss_gap']:+.4f}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {args.json_out}")


def run_refill(args):
    """Slot-refill search vs plain halving on the SAME rung ladder
    (core.lifecycle refill + search/*; DESIGN.md §13) → BENCH_refill.json.

    Both runs train the same ladder with the same AOT-compile-excluded
    timing as ``--halving``.  Plain halving shrinks the population at
    every rung (device utilisation decays down the ladder, and every
    post-rung segment re-compiles against the smaller layout); the
    constant-size refill prunes the same members but scatters PBT-style
    clones / fresh inits back into the freed slots IN PLACE, so every
    segment trains a full population with the ONE chunk executable
    compiled for segment 0 — the rung boundary pays eval + one jitted
    gather/scatter and ZERO recompilation.

    Tracked: models-explored-per-second (distinct members ever trained /
    end-to-end wall), the per-rung slot-utilisation curve, and a
    rung-boundary-overhead table (eval_s / update_s / compile_s,
    recompiled flag).  ABORTs unless the refill run strictly wins
    models/sec, matches-or-beats plain halving's best loss (survivors
    train identical trajectories, so refill can only add better
    newborns), and compiles exactly ONE chunk."""
    from repro.core import lifecycle
    from repro.core.selection import evaluate_population
    from repro.data import TabularTask
    from repro.search import RefillController, SearchSpace

    base = [(48, 24), (64, 32), (40, 16), (56, 28)]
    lp0 = LayeredPopulation.grid(
        20, 2, base, ("relu", "tanh"),
        repeats=max(args.members // (2 * len(base)), 1), block=args.block)
    schedule = lifecycle.HalvingSchedule.parse(args.refill_halving)
    total = args.refill_steps
    n0 = lp0.num_members
    task = TabularTask(4096, 20, n_classes=2, seed=0)
    _, (xte, yte) = task.split()
    xte, yte = jnp.asarray(xte), jnp.asarray(yte)
    n_rung = xte.shape[0]
    if args.rung_eval_batches:
        n_rung = min(n_rung, args.rung_eval_batches * args.batch)

    def batches(a, b):
        bs = [task.batch(s, args.batch) for s in range(a, b)]
        return (jnp.asarray(np.stack([x for x, _ in bs])),
                jnp.asarray(np.stack([y for _, y in bs])))

    def run(refill: bool):
        lp = lp0
        params = deep_mod.init_params(jax.random.PRNGKey(0), lp)
        controller = (RefillController(SearchSpace(), mode="pbt", seed=0)
                      if refill else None)
        member_ids = np.arange(n0)
        next_id = n0
        compiled = {}                 # (layout, scan) -> AOT executable
        wall = overhead = compile_s = 0.0
        pos = 0
        segs, rungs = [], []
        for i, (end, frac) in enumerate(schedule.segments(total)):
            key = (lp, end - pos)
            if key not in compiled:
                chunk = deep_mod.make_population_train_step(
                    lp, scan_steps=end - pos, donate=False)
                xs, ys = batches(pos, end)
                t0 = time.perf_counter()
                compiled[key] = chunk.lower(params, xs, ys, 0.05).compile()
                seg_compile = time.perf_counter() - t0
                compile_s += seg_compile
                if rungs:
                    # a segment recompiling right after a rung boundary is
                    # that boundary's layout-change cost — charge it there
                    rungs[-1]["compile_s"] = round(seg_compile, 4)
                    rungs[-1]["recompiled"] = True
            xs, ys = batches(pos, end)
            t0 = time.perf_counter()
            out = compiled[key](params, xs, ys, 0.05)
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            wall += dt
            params = out[0]
            segs.append({"seg": i, "steps": end - pos,
                         "members": lp.num_members,
                         "slot_utilisation": round(lp.num_members / n0, 4),
                         "wall_s": round(dt, 4),
                         "model_steps_per_s": round(
                             lp.num_members * (end - pos) / max(dt, 1e-12),
                             1)})
            pos = end
            if frac is None:
                continue
            # rung boundary — warm the per-layout eval jit first (the
            # compile-excluded convention of every bench in this file)
            evaluate_population(params, lp, xte[:n_rung], yte[:n_rung])
            t0 = time.perf_counter()
            losses, _ = evaluate_population(params, lp, xte[:n_rung],
                                            yte[:n_rung])
            keep = lifecycle.survivors(np.asarray(losses), frac)
            dt_eval = time.perf_counter() - t0
            n_pruned = lp.num_members - len(keep)
            if refill:
                plan = controller.plan(lp, np.asarray(losses), keep,
                                       member_ids, rung=i + 1,
                                       next_id=next_id, base_lr=0.05)
                fresh = None
                fm = plan.fresh_members
                if fm:
                    fresh = deep_mod.init_params(
                        jax.random.fold_in(jax.random.PRNGKey(0), 5000 + i),
                        LayeredPopulation(
                            lp.in_features, lp.out_features,
                            tuple(f.widths for f in fm),
                            tuple(f.acts for f in fm), block=lp.block))
                # warm the (lru-cached) scatter jit out of the timing
                lifecycle.refill_params(lp, params, plan.assignments, fresh)
                t1 = time.perf_counter()
                params = jax.block_until_ready(lifecycle.refill_params(
                    lp, params, plan.assignments, fresh))
                dt_upd = time.perf_counter() - t1
                member_ids = member_ids.copy()
                for f in plan.members:
                    member_ids[f.slot] = f.member_id
                next_id += len(plan.members)
            else:
                lifecycle.compact(lp, params, None, keep)   # warm
                t1 = time.perf_counter()
                lp, params, _ = lifecycle.compact(lp, params, None, keep)
                params = jax.block_until_ready(
                    jax.tree.map(jnp.asarray, params))
                dt_upd = time.perf_counter() - t1
                member_ids = member_ids[keep]
            overhead += dt_eval + dt_upd
            rungs.append({"step": end, "eval_s": round(dt_eval, 4),
                          "update_s": round(dt_upd, 4),
                          "compile_s": 0.0,
                          "pruned": int(n_pruned),
                          "recompiled": False})
            print(f"# {'refill' if refill else 'halving'} rung @ {end}: "
                  f"{len(keep)} kept, {lp.num_members} training on "
                  f"(eval {dt_eval*1e3:.0f} ms, update {dt_upd*1e3:.0f} ms)",
                  flush=True)
        losses, _ = evaluate_population(params, lp, xte, yte)
        return {"wall_s": round(wall, 3),
                "rung_overhead_s": round(overhead, 3),
                "compile_s": round(compile_s, 3),
                "chunk_compiles": len(compiled),
                "models_explored": int(next_id),
                "models_per_s": round(
                    next_id / max(wall + overhead, 1e-12), 3),
                "best_loss": round(float(np.min(np.asarray(losses))), 5),
                "segments": segs, "rungs": rungs}

    print(f"# population: {lp0.describe()}")
    print(f"# ladder: {schedule.rungs} over {total} steps")
    halv = run(refill=False)
    refl = run(refill=True)
    out = {
        "bench": "refill_search", "population": lp0.describe(),
        "batch": args.batch, "steps": total,
        "ladder": [list(r) for r in schedule.rungs],
        "halving": halv, "refill": refl,
        "models_per_s_ratio": round(
            refl["models_per_s"] / max(halv["models_per_s"], 1e-12), 3),
        "best_loss_gap": round(refl["best_loss"] - halv["best_loss"], 5),
        "note": "compile-excluded AOT timing as --halving; models/sec = "
                "distinct members ever trained / (train + rung overhead) "
                "wall; refill's chunk_compiles must stay 1 — the "
                "constant-size rung boundary is a compile-cache hit",
    }
    print(f"# halving: {halv['models_explored']} models, "
          f"{halv['models_per_s']}/s, best {halv['best_loss']:.4f}, "
          f"{halv['chunk_compiles']} compiles ({halv['compile_s']:.2f}s)")
    print(f"# refill:  {refl['models_explored']} models, "
          f"{refl['models_per_s']}/s, best {refl['best_loss']:.4f}, "
          f"{refl['chunk_compiles']} compile ({refl['compile_s']:.2f}s) "
          f"-> {out['models_per_s_ratio']}x models/s, "
          f"loss gap {out['best_loss_gap']:+.4f}")
    if refl["chunk_compiles"] != 1:
        raise SystemExit(f"ABORT: constant-size refill compiled "
                         f"{refl['chunk_compiles']} chunks (want exactly 1 "
                         "— the rung boundary must be a compile-cache hit)")
    if refl["models_per_s"] <= halv["models_per_s"]:
        raise SystemExit(
            f"ABORT: refill explored {refl['models_per_s']} models/s vs "
            f"halving's {halv['models_per_s']} — the refill path must "
            "strictly win exploration throughput")
    if refl["best_loss"] > halv["best_loss"] + 1e-6:
        raise SystemExit(
            f"ABORT: refill best loss {refl['best_loss']} worse than "
            f"halving's {halv['best_loss']} — survivors train identical "
            "trajectories, so refill must match-or-beat")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {args.json_out}")


def run_pipeline(args):
    """Streaming-data-plane bench (DESIGN.md §11) → BENCH_pipeline.json.

    The SAME AOT-compiled scan chunk over the SAME step-indexed batches,
    driven two ways:

      sync     — the pre-§11 driver loop: build the chunk's batches on the
                 consumer thread (the paper-task batch is a fresh
                 permutation over the whole sample set — real host work),
                 stack, device_put, dispatch, then BLOCK on the chunk's
                 per-member losses before building the next chunk.
      prefetch — ``data.pipeline.Prefetcher``: a producer thread stages
                 chunk c+1 into alternating host buffers and device_puts
                 it while chunk c executes; each chunk's loss fetch is
                 deferred until the next chunk is dispatched.

    Reports steps/s for both, the device-idle fraction of each (estimated
    against a pre-staged all-on-device dispatch loop = pure device time),
    and ABORTS unless (a) the two paths end bit-identical and (b) prefetch
    strictly wins wall-clock — the overlap claim is only committed as an
    artifact when it is true on this host."""
    from repro.data import TabularTask
    from repro.data.pipeline import Prefetcher

    lp, mesh, shardings, ctx = _deep_bench_population(args)
    scan = args.scan_steps
    n_chunks = args.pipeline_chunks
    B = args.batch
    lr = 0.05
    task = TabularTask(args.pipeline_samples, lp.in_features,
                       n_classes=lp.out_features, seed=0)
    sh_x = sh_y = None
    if args.sharded:
        from repro.distributed.sharding import population_batch_shardings
        sh_x, sh_y = population_batch_shardings(mesh, B)

    def dput(x, sh):
        return jax.device_put(x, sh) if sh is not None else jax.device_put(x)

    with ctx:
        params0 = deep_mod.init_params(jax.random.PRNGKey(0), lp)
        if shardings is not None:
            params0 = jax.device_put(params0, shardings)
        chunk = deep_mod.make_population_train_step(
            lp, scan_steps=scan, donate=False)
        bx0, by0 = task.batch(0, B)
        compiled = chunk.lower(
            params0, jax.ShapeDtypeStruct((scan,) + bx0.shape, bx0.dtype),
            jax.ShapeDtypeStruct((scan,) + by0.shape, by0.dtype),
            lr).compile()

        def make_staging():
            return (np.empty((scan,) + bx0.shape, bx0.dtype),
                    np.empty((scan,) + by0.shape, by0.dtype))

        def build_slab(c, staging):
            # the §11 producer body: slab-granular build (epoch permutation
            # amortized across the chunk) into reusable staging, then
            # device_put the SNAPSHOT — never the staging buffer itself
            # (sharded device_put may zero-copy alias; aliasing rule)
            sx, sy = staging
            task.batch_slab(c * scan, scan, B, out=(sx, sy))
            return dput(np.array(sx), sh_x), dput(np.array(sy), sh_y)

        def run_sync(params):
            # faithful pre-§11 driver chunk loop (launch/train.py before
            # the streaming data plane): per-step random-access batch()
            # calls — each re-deriving its epoch's n-sample permutation —
            # np.stack, device_put, dispatch, then a BLOCKING per-chunk
            # metrics fetch that drains the pipeline before the next build
            losses = []
            t0 = time.perf_counter()
            for c in range(n_chunks):
                bs = [task.batch(c * scan + i, B) for i in range(scan)]
                xs = dput(np.stack([b[0] for b in bs]), sh_x)
                ys = dput(np.stack([b[1] for b in bs]), sh_y)
                params, _, pers = compiled(params, xs, ys, lr)
                losses.append(float(np.asarray(pers)[-1].mean()))
            jax.block_until_ready(params)
            return params, losses, time.perf_counter() - t0

        def run_sync_slab(params):
            # decomposition diagnostic: the slab-granular build WITHOUT the
            # producer thread or deferred metrics — isolates how much of
            # the prefetch win is build amortization vs overlap on this
            # host (a 1-core box shows ~all amortization; overlap needs
            # spare cores to hide the build behind the chunk)
            staging = make_staging()
            losses = []
            t0 = time.perf_counter()
            for c in range(n_chunks):
                xs, ys = build_slab(c, staging)
                params, _, pers = compiled(params, xs, ys, lr)
                losses.append(float(np.asarray(pers)[-1].mean()))
            jax.block_until_ready(params)
            return params, losses, time.perf_counter() - t0

        def run_prefetch(params):
            losses, pending = [], None
            pf = Prefetcher(build_slab, n_chunks,
                            make_staging=make_staging,
                            depth=args.prefetch_depth)
            try:
                t0 = time.perf_counter()
                for c in range(n_chunks):
                    xs, ys = pf.get(c)
                    params, _, pers = compiled(params, xs, ys, lr)
                    if pending is not None:   # chunk c-1's deferred fetch
                        losses.append(float(np.asarray(pending)[-1].mean()))
                    pending = pers
                losses.append(float(np.asarray(pending)[-1].mean()))
                jax.block_until_ready(params)
                return params, losses, time.perf_counter() - t0
            finally:
                pf.close()

        def run_devbound(params):
            # pure device time: every slab pre-staged, one terminal block —
            # the idle-fraction denominator (what a perfect data plane
            # would leave)
            staging = make_staging()
            slabs = [build_slab(c, staging) for c in range(n_chunks)]
            jax.block_until_ready(slabs)
            t0 = time.perf_counter()
            for xs, ys in slabs:
                params, _, pers = compiled(params, xs, ys, lr)
            jax.block_until_ready(params)
            return time.perf_counter() - t0

        # warm everything once (compile is AOT, but first-touch costs —
        # thread spin-up, allocator, epoch-order cache — must not land on
        # a timed rep)
        run_sync(params0)
        run_prefetch(params0)
        run_devbound(params0)

        sync_walls, slab_walls, pre_walls, dev_walls = [], [], [], []
        for _ in range(args.pipeline_reps):
            p_sync, l_sync, w = run_sync(params0)
            sync_walls.append(w)
            p_slab, l_slab, w = run_sync_slab(params0)
            slab_walls.append(w)
            p_pre, l_pre, w = run_prefetch(params0)
            pre_walls.append(w)
            dev_walls.append(run_devbound(params0))
        sync_wall, pre_wall = min(sync_walls), min(pre_walls)
        slab_wall, dev_wall = min(slab_walls), min(dev_walls)

        for name, p_other in (("slab", p_slab), ("prefetched", p_pre)):
            if not all(np.array_equal(np.asarray(a), np.asarray(b))
                       for a, b in zip(jax.tree.leaves(p_sync),
                                       jax.tree.leaves(p_other))):
                raise SystemExit(
                    f"{name} run is NOT bit-identical to the synchronous "
                    "driver — the data plane changed the trajectory (§11 "
                    "contract violated); refusing to publish numbers")
        if not (l_sync == l_slab == l_pre):
            raise SystemExit(
                "deferred metrics diverged from the synchronous fetches: "
                f"{l_sync} vs {l_slab} vs {l_pre}")

    steps = n_chunks * scan
    out = {
        "bench": "pipeline", "population": lp.describe(),
        "batch": B, "scan_steps": scan, "chunks": n_chunks,
        "samples": args.pipeline_samples,
        "prefetch_depth": args.prefetch_depth,
        "reps": args.pipeline_reps,
        "sync": {"wall_s": round(sync_wall, 4),
                 "steps_per_s": round(steps / sync_wall, 2),
                 "device_idle_frac": max(
                     0.0, round(1 - dev_wall / sync_wall, 4))},
        "prefetch": {"wall_s": round(pre_wall, 4),
                     "steps_per_s": round(steps / pre_wall, 2),
                     "device_idle_frac": max(
                         0.0, round(1 - dev_wall / pre_wall, 4))},
        "sync_slab_wall_s": round(slab_wall, 4),
        "device_bound_wall_s": round(dev_wall, 4),
        "speedup": round(sync_wall / pre_wall, 4),
        "bit_identical": True,
        "sharded": bool(args.sharded),
        "mesh": dict(mesh.shape) if mesh else None,
        "note": "sync = the pre-§11 driver loop (per-step batch() calls, "
                "each re-deriving its epoch permutation, np.stack, "
                "device_put, blocking per-chunk metrics fetch); prefetch = "
                "the §11 data plane (producer-thread slab-granular build, "
                "double-buffered staging, deferred metrics). "
                "sync_slab_wall_s isolates the slab-build amortization "
                "without the producer thread — the prefetch-vs-sync_slab "
                "gap is the overlap contribution, which needs spare host "
                "cores to show. device_idle_frac = 1 - "
                "device_bound_wall/wall, where device_bound_wall "
                "dispatches pre-staged slabs with one terminal block "
                "(pure device time at these shapes)",
    }
    print(f"# sync      {out['sync']['steps_per_s']} steps/s "
          f"(idle {out['sync']['device_idle_frac']:.1%})")
    print(f"# sync+slab {round(steps / slab_wall, 2)} steps/s "
          f"(no producer thread)")
    print(f"# prefetch  {out['prefetch']['steps_per_s']} steps/s "
          f"(idle {out['prefetch']['device_idle_frac']:.1%}) -> "
          f"{out['speedup']}x, bit-identical", flush=True)
    if pre_wall >= sync_wall:
        raise SystemExit(
            f"prefetch does NOT strictly beat the synchronous driver "
            f"({pre_wall:.4f}s vs {sync_wall:.4f}s) — refusing to commit "
            "a no-win artifact")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {args.json_out}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--members", type=int, default=300)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--block", type=int, default=8)
    ap.add_argument("--impls", nargs="+", default=sorted(M3_IMPLS))
    ap.add_argument("--deep", action="store_true",
                    help="bench the layered engine (BD_IMPLS shoot-out) "
                         "instead of the single-layer M3 variants")
    ap.add_argument("--fused", action="store_true",
                    help="bench the one-pass fused path against pallas "
                         "(+seg_act round trip) and einsum, f32 AND bf16, "
                         "with per-phase kernel-launch counts, roofline "
                         "coordinates, and the batch sweep "
                         "-> BENCH_fused.json")
    ap.add_argument("--sweep-batches", nargs="+", type=int,
                    default=[32, 256, 1024],
                    help="--fused: batch sizes for the launch-budget sweep "
                         "(counts must be IDENTICAL across all of them)")
    ap.add_argument("--sweep-launches-only", action="store_true",
                    help="--fused: skip the sweep's large-batch wall-clock "
                         "measurements (interpret mode is slow there) and "
                         "record only the trace-derived launch counts")
    ap.add_argument("--bd-impls", nargs="+", default=None,
                    help="mid-layer impls to bench (unknown impls ABORT; "
                         "default: einsum+pallas for --deep, all three "
                         "for --fused)")
    ap.add_argument("--sharded", action="store_true",
                    help="--deep: run under the host mesh (shard-padded "
                         "population axis; fake devices via XLA_FLAGS)")
    ap.add_argument("--scan-steps", type=int, default=8,
                    help="--deep: chunk size for the scan-vs-loop "
                         "train-step bench")
    ap.add_argument("--serve", action="store_true",
                    help="bench the forward-only serving path: infer "
                         "launch budget (depth+1, no residual outputs), "
                         "forward-only vs training-forward-reuse wall/HBM, "
                         "and p50/p99 + req/s vs ensemble size "
                         "-> BENCH_serve.json")
    ap.add_argument("--serve-requests", type=int, default=256,
                    help="--serve: requests through the batching loop "
                         "per ensemble mode")
    ap.add_argument("--fwd-batch", type=int, default=256,
                    help="--serve: batch for the forward-only vs "
                         "train-reuse proof (residual buffers scale with "
                         "batch, so this is a serving-slab size, decoupled "
                         "from the latency loop's --batch)")
    ap.add_argument("--topk", type=int, default=4,
                    help="--serve: ensemble size for the top-k mode")
    ap.add_argument("--max-latency-ms", type=float, default=5.0,
                    help="--serve: flush timer for partial batches")
    ap.add_argument("--quant", action="store_true",
                    help="bench the int8 weight-only serve copy (DESIGN.md "
                         "§12) against the bf16 half-width store at "
                         "--fwd-batch: wall + loop-aware HLO HBM (int8 must "
                         "STRICTLY win both or ABORT), depth+1 launch "
                         "budget under the fused-dequant kernels, and "
                         "per-ensemble-mode calibration accuracy vs f32 "
                         "(|delta| > 0.5%% ABORTS) -> BENCH_quant.json")
    ap.add_argument("--quant-calib", type=int, default=1024,
                    help="--quant: calibration samples for the accuracy "
                         "gate")
    ap.add_argument("--quant-train-steps", type=int, default=64,
                    help="--quant: sgd steps before quantizing, so the "
                         "accuracy gate scores trained decision margins "
                         "(0 skips training)")
    ap.add_argument("--optim", action="store_true",
                    help="bench the stateful-optimizer engine: the scanned "
                         "chunk under sgd/momentum/adamw (f32 + bf16 "
                         "moments), per-step wall + opt-state HBM overhead "
                         "-> BENCH_optim.json")
    ap.add_argument("--halving", nargs="?", const="16:0.25,32:0.25",
                    default=None, metavar="RUNGS",
                    help="bench the successive-halving lifecycle vs "
                         'full-population training (rungs "STEP:KEEP,...", '
                         "default 16:0.25,32:0.25) -> BENCH_halving.json")
    ap.add_argument("--halving-steps", type=int, default=96,
                    help="--halving: total optimizer steps for both runs")
    ap.add_argument("--rung-eval-batches", type=int, default=0,
                    help="--halving: evaluate only this many --batch-sized "
                         "eval batches at each rung boundary (0 = full "
                         "split; the final selection eval is always full)")
    ap.add_argument("--refill", action="store_true",
                    help="bench the constant-size slot-refill search vs "
                         "plain halving on the same rung ladder (DESIGN.md "
                         "§13): models-explored/sec, per-rung slot "
                         "utilisation, zero-recompile rung boundaries -> "
                         "BENCH_refill.json (ABORTS unless refill strictly "
                         "wins models/sec, matches-or-beats best loss, and "
                         "compiles exactly one chunk)")
    ap.add_argument("--refill-steps", type=int, default=48,
                    help="--refill: total optimizer steps for both runs")
    ap.add_argument("--refill-halving", default="12:0.5,24:0.5,36:0.5",
                    metavar="RUNGS",
                    help='--refill: rung ladder "STEP:KEEP,..." shared by '
                         "both runs (equal-length segments keep scan_steps "
                         "constant so the refill path needs ONE chunk)")
    ap.add_argument("--pipeline", action="store_true",
                    help="bench the streaming data plane (DESIGN.md §11): "
                         "synchronous build->dispatch->blocking-fetch driver "
                         "loop vs data.pipeline.Prefetcher with deferred "
                         "metrics, same AOT chunk, bit-identical params "
                         "asserted -> BENCH_pipeline.json (ABORTS unless "
                         "prefetch strictly wins wall-clock)")
    ap.add_argument("--pipeline-chunks", type=int, default=16,
                    help="--pipeline: scan chunks per timed run")
    ap.add_argument("--pipeline-samples", type=int, default=262144,
                    help="--pipeline: task sample-set size — batch build "
                         "permutes the whole set per step, so this sets how "
                         "much real host work the prefetcher must hide")
    ap.add_argument("--pipeline-reps", type=int, default=3,
                    help="--pipeline: timed reps per path (best-of)")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="--pipeline: producer queue bound (2 = double "
                         "buffering)")
    ap.add_argument("--json-out", default=None,
                    help="write results as JSON (BENCH_*.json tracking)")
    args = ap.parse_args(argv)

    if args.pipeline:
        if args.json_out is None:
            args.json_out = "BENCH_pipeline.json"
        run_pipeline(args)
        return
    if args.quant:
        if args.json_out is None:
            args.json_out = "BENCH_quant.json"
        run_quant(args)
        return
    if args.serve:
        if args.json_out is None:
            args.json_out = "BENCH_serve.json"
        run_serve(args)
        return
    if args.optim:
        if args.json_out is None:
            args.json_out = "BENCH_optim.json"
        run_optim(args)
        return
    if args.refill:
        if args.json_out is None:
            args.json_out = "BENCH_refill.json"
        run_refill(args)
        return
    if args.halving:
        if args.json_out is None:
            args.json_out = "BENCH_halving.json"
        run_halving(args)
        return
    if args.fused:
        if args.json_out is None:
            args.json_out = "BENCH_fused.json"
        run_fused(args)
        return
    if args.deep:
        if args.json_out is None:
            args.json_out = "BENCH_deep.json"
        args.bd_impls = args.bd_impls or ["einsum", "pallas"]
        run_deep(args)
        return

    hidden = range(1, args.members // 10 + 1)
    pop = Population.grid(100, 2, hidden, PAPER_TEN, repeats=1,
                          block=args.block)
    print(f"# population: {pop.describe()}")
    print("impl,wall_ms,dot_gflops,hbm_mb")
    for impl in args.impls:
        wall, stats = bench(pop, args.batch, impl)
        print(f"{impl},{wall*1e3:.2f},{stats['flops']/1e9:.3f},"
              f"{stats['hbm_bytes']/1e6:.1f}", flush=True)


if __name__ == "__main__":
    main()
