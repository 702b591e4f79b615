"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Runs REAL training at whatever scale the current device set supports
(reduced configs on CPU; the full configs on an actual pod — the code path
is identical, only the mesh differs).  Wires together:

  data (step-indexed, restart-safe) → train_step (jit, sharded) →
  TrainRunner (checkpoint/restart, straggler watchdog) → metrics log

Flags exercise every distributed feature: --compress-grads (int8 cross-pod
all-reduce), --ckpt-every / --resume.

Population archs (``--arch parallelmlp-10k``) train through the layered
population engine (core.deep): ``--population-depths "64,32,16;13,5;7"``
builds a heterogeneous-depth LayeredPopulation (members separated by ';',
per-layer widths by ','), ``--bd-impl fused`` runs every layer through the
fused Pallas kernels (``einsum``, the default, through XLA ops),
``--per-member-lr`` samples one step size per member, and checkpoints carry the fused layout
(checkpoint.save_population) so ``--resume`` needs no flags re-supplied.
The population path is distribution-native: the layout shard-pads to the
mesh's 'model' axis, params are born sharded, batches shard over 'data',
the step is a donated ``lax.scan`` chunk (``--scan-steps``), and the loop
runs through ``TrainRunner`` exactly like the LM path.  ``--halving
"500:0.5,1000:0.25"`` adds the successive-halving lifecycle: prune at each
rung, compact the survivors into a smaller fused layout, continue.
``--optimizer {sgd,momentum,adamw,adafactor}`` selects the stateful
optimizer engine (DESIGN.md §8): opt state is born sharded, compacted
through rungs, checkpointed, and validated on resume; ``--per-member-lr``
/ ``--per-member-momentum`` / ``--per-member-weight-decay`` race
heterogeneous training recipes across the population; ``--grad-clip``
clips by global norm and logs the pre-clip norm per step.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import set_mesh
from jax.profiler import StepTraceAnnotation

from repro.checkpoint import latest_steps, restore
from repro.configs import get_arch
from repro.core.deep import BD_CHOICES
from repro.data import TabularTask, TokenTask
from repro.distributed import TrainRunner, StragglerPolicy
from repro.distributed.sharding import logical_to_sharding
from repro.launch.cache import configure_compile_cache
from repro.launch.cells import build_optimizer
from repro.launch.mesh import make_host_mesh
from repro.models import encdec, lm
from repro.optim import warmup_cosine


class ChunkTrace:
    """``--trace-dir``: a profiler trace of chunks ``FIRST``..``LAST`` of
    the first segment, after chunk 0's compile, so that the ``train_chunk``
    steps, the data plane's spans and the named kernels share one clock.
    The trace ends when chunk ``LAST`` has finished on the device, or with
    the segment; no later segment is traced."""

    FIRST, LAST = 1, 3

    def __init__(self, trace_dir):
        self.dir = trace_dir
        self.on = False
        self.done = trace_dir is None

    def chunk_start(self, c, state):
        """Called before chunk ``c`` with its input ``state``."""
        if self.on and c > self.LAST:
            jax.block_until_ready(state)
            self.stop()
        elif not self.done and not self.on and c == self.FIRST:
            jax.profiler.start_trace(self.dir)
            self.on = True

    def stop(self):
        if self.on:
            jax.profiler.stop_trace()
            self.on = False
        self.done = True


def _init_sharded(init_fn, specs_fn, mesh):
    """jit the initializer with out_shardings so parameters are BORN sharded
    (no host-side full materialisation)."""
    abs_p, specs = specs_fn()
    sh = logical_to_sharding(specs, mesh, abs_p)
    return jax.jit(init_fn, out_shardings=sh)(jax.random.PRNGKey(0)), sh


def run_lm(arch, args, mesh):
    cfg = arch.model
    is_encdec = arch.kind == "encdec"
    mod = encdec if is_encdec else lm
    with set_mesh(mesh):
        params, p_sh = _init_sharded(
            lambda k: mod.init_params(k, cfg)[0],
            lambda: mod.abstract_params(cfg), mesh)
        opt = build_optimizer(arch)
        o_specs = opt.state_specs(mod.abstract_params(cfg)[1],
                                  mod.abstract_params(cfg)[0])
        abs_o = jax.eval_shape(opt.init, params)
        o_sh = logical_to_sharding(o_specs, mesh, abs_o)
        opt_state = jax.jit(opt.init, out_shardings=o_sh)(params)

        lr_fn = warmup_cosine(arch.lr, args.warmup, args.steps)
        # LM default stays 1.0 when the flag is unset (populations default
        # to clipping OFF — plain SGD baselines must stay bit-exact)
        step_fn_raw = mod.make_train_step(
            cfg, opt, lr_fn, num_micro=args.num_micro, mesh=mesh,
            grad_clip=1.0 if args.grad_clip is None else args.grad_clip)
        jit_step = jax.jit(step_fn_raw, donate_argnums=(0, 1))

        task = TokenTask(vocab=cfg.vocab, seed=args.seed)

        def make_batch(step):
            b = task.batch(step, args.batch, args.seq)
            if is_encdec:
                rng = np.random.default_rng([args.seed, step])
                b["frames"] = rng.normal(
                    0, 1, (args.batch, args.seq, cfg.d_model)
                ).astype(np.float32)
            elif cfg.frontend == "embeds":
                rng = np.random.default_rng([args.seed, step])
                b["embeds"] = rng.normal(
                    0, 1, (args.batch, args.seq, cfg.d_model)
                ).astype(np.float32)
                del b["tokens"]
            return b

        state = {"params": params, "opt": opt_state}

        def step_fn(state, step):
            batch = make_batch(step)
            p, o, metrics = jit_step(state["params"], state["opt"], batch,
                                     jnp.asarray(step, jnp.int32))
            return {"params": p, "opt": o}, {
                k: float(v) for k, v in metrics.items()}

        runner = TrainRunner(
            step_fn, state, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every,
            straggler=StragglerPolicy(timeout_s=args.straggler_timeout),
            mesh=mesh, state_specs={"params": mod.abstract_params(cfg)[1],
                                    "opt": o_specs})
        start = 0
        if args.resume and latest_steps(args.ckpt_dir):
            # restore through the runner's derived sharding tree so resume
            # lands sharded (replicating params+opt first OOMs exactly the
            # configs the mesh exists for)
            runner.state, last = restore(args.ckpt_dir, runner.state,
                                         shardings=runner.restore_shardings)
            start = last + 1
            print(f"resumed from step {last}")
        t0 = time.time()
        runner.run(args.steps, start_step=start)
        dt = time.time() - t0
        losses = [m["loss"] for _, m in runner.metrics_log]
        print(f"done: {len(losses)} steps in {dt:.1f}s; "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
        return runner


def parse_depth_spec(spec: str):
    """"64,32,16;13,5;7" → ((64, 32, 16), (13, 5), (7,)) — one member per
    ';'-separated group, one hidden layer per ','-separated width."""
    widths = []
    for member in spec.split(";"):
        member = member.strip()
        if not member:
            continue
        widths.append(tuple(int(w) for w in member.split(",")))
    if not widths:
        raise ValueError(f"empty population spec {spec!r}")
    return tuple(widths)


def run_population(arch, args, report=None, mesh=None):
    """Fused population training through the layered engine (core.deep),
    DISTRIBUTION-NATIVE: the layout is shard-padded to the mesh's
    population ('model') axis, parameters are born sharded through
    ``LayeredPopulation.param_specs()``, the step is a jitted
    argument-donating ``lax.scan`` chunk (``--scan-steps``), train batches
    shard over the 'data' axis, and the loop runs through ``TrainRunner``
    (checkpoint cadence, straggler watchdog, sharded crash replay) with
    layout-carrying sharded checkpoints.

    ``--halving`` drives the successive-halving lifecycle (core.lifecycle,
    DESIGN.md §6): the run is split into rung segments; at each rung
    boundary the loop exits the donated scan chunk, evaluates under the
    training sharding, prunes to the best ``keep_frac`` of the survivors,
    COMPACTS them into a freshly bucketed layout, re-pads it to the mesh,
    device_puts the gathered state born-sharded, and re-jits the next
    segment's chunk against the physically smaller population.  Checkpoints
    carry the lifecycle (rung index + survivor→original member mapping), so
    ``--resume`` restores mid-ladder on the compacted layout and the
    leaderboard keeps reporting ORIGINAL member ids.

    The step itself is OPTIMIZER-GENERIC (core.deep.opt_step engine,
    DESIGN.md §8): ``--optimizer {sgd,momentum,adamw,adafactor}`` carries
    ``(params, opt_state)`` through the donated scan chunk, with the state
    born sharded through ``LayeredPopulation.opt_specs()``, compacted
    through halving rung boundaries (real moments, not just params), saved
    with every checkpoint (+ the optimizer config in ``meta["train"]``,
    validated on resume), and per-member hyperparameter vectors
    (``--per-member-lr``/``--per-member-momentum``/
    ``--per-member-weight-decay``) so members race heterogeneous training
    RECIPES, not just architectures.  Plain ``sgd`` reproduces the
    historical stateless trajectory bit-for-bit.

    ``report`` (optional dict) receives the run's counters: ``restarts``
    (TrainRunner replays, summed over segments), ``compile_s`` (wall of the
    first chunk call — trace + compile + dispatch), ``first_loss`` /
    ``last_loss`` (mean over real members) and ``per_member_last`` (the
    last step's per-member losses, real members).  ``mesh`` overrides the
    default ``make_host_mesh()`` over every local device."""
    from repro.checkpoint import (latest_steps, layout_from_meta,
                                  lifecycle_from_meta, load_meta,
                                  population_meta, require_optimizer_match,
                                  restore_population, save_population)
    from repro.core import deep
    from repro.core.activations import PAPER_TEN
    from repro.core.lifecycle import (HalvingSchedule, compact,
                                      compact_factored, grow_params,
                                      refill_params, refill_state, survivors)
    from repro.core.population import LayeredPopulation, Population
    from repro.core.selection import evaluate_population, leaderboard
    from repro.data import DeferredMetrics, Prefetcher, TabularTask
    from repro.distributed import StragglerPolicy, TrainRunner
    from repro.distributed.sharding import (pop_axis_size,
                                            population_batch_shardings,
                                            population_opt_shardings,
                                            population_shardings)
    from repro.launch.mesh import make_host_mesh
    from repro.optim import adafactor, adamw, sgd
    from repro.search import RefillController, SearchSpace

    schedule = HalvingSchedule.parse(args.halving) if args.halving else None

    # ---- slot-refill search controller (DESIGN.md §13): prune-then-refill
    # at every rung boundary.  "pbt" holds the population size constant
    # (refills adopt their slot's architecture — the zero-re-jit path);
    # "arch" resamples architectures from the space and grows the layout.
    refill_mode = args.refill
    space = SearchSpace.parse(args.search_space)
    controller = None
    if refill_mode != "off":
        if schedule is None:
            raise SystemExit("--refill needs --halving (rung boundaries "
                             "are where slots free up)")
        controller = RefillController(space, mode=refill_mode,
                                      seed=args.seed,
                                      exploit_frac=args.refill_exploit_frac)

    # ---- optimizer config (resolved before any state is materialised so
    # the resume path can validate it against the checkpoint's record)
    opt_name = args.optimizer or arch.optimizer
    grad_clip = args.grad_clip if args.grad_clip else None
    if opt_name not in ("sgd", "momentum", "adamw", "adafactor"):
        raise SystemExit(f"unknown optimizer {opt_name!r}")
    if args.per_member_momentum and opt_name != "momentum":
        raise SystemExit("--per-member-momentum needs --optimizer momentum")
    if args.per_member_weight_decay and opt_name not in ("adamw",
                                                         "adafactor"):
        raise SystemExit(
            "--per-member-weight-decay needs --optimizer adamw/adafactor")
    if args.per_member_weight_decay and args.weight_decay <= 0:
        raise SystemExit("--per-member-weight-decay scales --weight-decay; "
                         "set it > 0")
    if args.opt_state_dtype != "float32" and opt_name != "adamw":
        raise SystemExit(
            "--opt-state-dtype applies to --optimizer adamw only "
            "(sgd/momentum moments are f32; adafactor manages its own "
            "state dtypes) — it would be silently ignored here")
    # the record checkpoints carry under meta["train"]["optimizer"]: resume
    # must match it EXACTLY or fail loudly (require_optimizer_match) — a
    # state tree reinterpreted under different hyperparameters is silent
    # corruption
    opt_record = {
        "name": opt_name, "lr": float(arch.lr),
        "grad_clip": float(grad_clip or 0.0),
        "per_member_lr": bool(args.per_member_lr),
        "per_member_momentum": bool(args.per_member_momentum),
        "per_member_weight_decay": bool(args.per_member_weight_decay),
    }
    if opt_name == "momentum":
        opt_record["momentum"] = float(args.momentum)
    if opt_name in ("adamw", "adafactor"):
        opt_record["weight_decay"] = float(args.weight_decay)
    if opt_name == "adamw":
        opt_record["state_dtype"] = args.opt_state_dtype
    if (args.per_member_lr or args.per_member_momentum
            or args.per_member_weight_decay):
        # per-member vectors are pure functions of (seed, n0): resuming
        # under a different seed would silently redraw every member's
        # recipe beneath the restored moments, so the seed is part of the
        # optimizer config whenever a vector is in play
        opt_record["seed"] = int(args.seed)
    if refill_mode != "off":
        # a resumed refill run must re-plan future rungs identically (the
        # controller rng folds the seed) and must not reinterpret grown
        # recipe vectors under a different space or mode
        opt_record["refill"] = refill_mode
        opt_record["seed"] = int(args.seed)
        if args.search_space:
            opt_record["search_space"] = args.search_space

    if args.population_depths:
        widths = parse_depth_spec(args.population_depths)
        acts = tuple(a.strip() for a in args.population_acts.split(","))
        if acts == ("paper",):
            acts = PAPER_TEN
        lp = LayeredPopulation(
            args.population_features, args.population_classes,
            widths * args.population_repeats,
            tuple(acts[i % len(acts)]
                  for i in range(len(widths) * args.population_repeats)),
            block=args.population_block).sorted()
    else:
        model = arch.model
        lp = model.layered() if isinstance(model, Population) else model

    if mesh is None:
        mesh = make_host_mesh()
    scan = max(args.scan_steps, 1)
    print(f"mesh={dict(mesh.shape)} devices={len(jax.devices())} "
          f"scan_steps={scan}")

    with set_mesh(mesh):
        start = 0
        rung = 0
        resuming = bool(args.resume and latest_steps(args.ckpt_dir))
        legacy_ckpt = False
        if resuming:
            # resolve the checkpoint's layout + lifecycle + optimizer
            # record from the META first: the per-member hyperparameter
            # vectors (drawn over n0) and the abstract optimizer state are
            # needed BEFORE the arrays can restore sharded
            meta, last = load_meta(args.ckpt_dir)
            stored = require_optimizer_match(meta, opt_record)
            legacy_ckpt = (stored is None
                           or meta["population"].get("schema",
                                                     "layered") == "single")
            if legacy_ckpt and opt_name != "sgd":
                raise SystemExit(
                    f"--resume: the checkpoint at step {last} predates the "
                    "stateful-optimizer engine (no optimizer state saved); "
                    "it can only resume with the stateless "
                    "'--optimizer sgd'")
            lp_meta = layout_from_meta(meta)
            rung, member_ids, n0 = lifecycle_from_meta(meta, lp_meta)
            start = last + 1
        else:
            lp_real, lp = lp, lp.shard_pad(pop_axis_size(mesh))
            n0 = lp_real.num_members
            member_ids = np.arange(n0)

        # ---- per-member hyperparameter vectors: each drawn ONCE over the
        # run's ORIGINAL n0 members — through the declarative search space
        # (search/space.py; the default space reproduces the historical
        # hardcoded ranges BIT-FOR-BIT) — and indexed down by the survivor
        # mapping (shard-pad fillers get the base value): a member keeps
        # its training recipe through every compaction and across resumes,
        # identically to a single-device run.  With --refill the vectors
        # are GROWABLE numpy arrays indexed by original id: every refilled
        # member appends its (perturbed or freshly sampled) recipe at its
        # fresh id, and the grown tails ride the checkpoint meta so a
        # resume never redraws them.
        lr0 = mom0 = wd0 = None
        if args.per_member_lr:
            lr0 = np.asarray(space.init_lr(args.seed, n0, arch.lr))
            print(f"per-member learning rates in "
                  f"[{arch.lr * space.lr_scale[0]:.4f}, "
                  f"{arch.lr * space.lr_scale[1]:.4f}]")
        if args.per_member_momentum:
            mom0 = np.asarray(space.init_momentum(args.seed, n0))
            print(f"per-member momentum in [{space.momentum_range[0]:.2f}, "
                  f"{space.momentum_range[1]:.2f}]")
        if args.per_member_weight_decay:
            wd0 = np.asarray(space.init_wd(args.seed, n0,
                                           args.weight_decay))
            print(f"per-member weight decay in "
                  f"[{args.weight_decay * space.wd_scale[0]:.5f}, "
                  f"{args.weight_decay * space.wd_scale[1]:.5f}]")

        # ---- lineage: original id → (parent id, birth rung); ids issued
        # from a monotone counter strictly above every id ever used, so a
        # member born at rung r can never alias a pruned seed's id
        next_id = int(n0)
        lineage = {}
        if resuming and refill_mode != "off":
            life = meta.get("lifecycle") or {}
            next_id = int(life.get("next_id", n0))
            lineage = {int(k): (int(v[0]), int(v[1]))
                       for k, v in (life.get("lineage") or {}).items()}
            if lr0 is not None and "lr_vec" in life:
                lr0 = np.asarray(life["lr_vec"], lr0.dtype)
            if mom0 is not None and "mom_vec" in life:
                mom0 = np.asarray(life["mom_vec"], mom0.dtype)
            if wd0 is not None and "wd_vec" in life:
                wd0 = np.asarray(life["wd_vec"], wd0.dtype)

        def member_vec(vec0, base, lp):
            v = jnp.asarray(vec0)[jnp.asarray(member_ids)]
            return jnp.concatenate(
                [v, jnp.full((lp.n_pad,), base, v.dtype)])

        def member_lr(lp):
            return arch.lr if lr0 is None else member_vec(lr0, arch.lr, lp)

        # bumped on every build_opt call: part of the chunk-cache key, so a
        # rebuilt optimizer (new baked momentum/decay trees) re-specializes
        # the chunk while an UNCHANGED (lp, opt) pair is a guaranteed
        # compile-cache hit — the constant-size refill's zero-re-jit path
        opt_epoch = 0

        def build_opt(lp):
            """The segment's optimizer: per-member hyper vectors indexed
            down through the survivor mapping and expanded to scale trees
            for THIS layout — rebuilt at every rung boundary that changes
            the layout or the baked recipe trees, exactly like the
            re-jitted chunk.  NOT rebuilt by a constant-size lr-only
            refill: lr is a runtime chunk argument, so mutating it needs
            no new optimizer and no re-trace."""
            nonlocal opt_epoch
            opt_epoch += 1
            mom = (args.momentum if mom0 is None else
                   deep.member_lr_tree(lp, member_vec(mom0, args.momentum,
                                                      lp)))
            wd = (args.weight_decay if wd0 is None else
                  deep.member_lr_tree(lp, member_vec(wd0, args.weight_decay,
                                                     lp)))
            if opt_name == "sgd":
                return sgd()
            if opt_name == "momentum":
                return sgd(momentum=mom)
            if opt_name == "adamw":
                return adamw(weight_decay=wd,
                             state_dtype=jnp.dtype(args.opt_state_dtype))
            return adafactor(weight_decay=wd)

        # ---- materialise (params, opt_state), born sharded either way
        if resuming:
            # the checkpoint's layout wins (it matches the stored params
            # and is already shard-padded for the mesh that wrote it);
            # restore straight onto THIS mesh through its param/opt specs.
            opt = build_opt(lp_meta)
            opt_state = None
            if legacy_ckpt:
                params, lp_ckpt, _ = restore_population(args.ckpt_dir,
                                                        mesh=mesh)
                if isinstance(lp_ckpt, Population):
                    # single-layer (parallel_mlp) checkpoint → depth-1
                    # layered params map one-to-one onto the unified engine
                    lp_ckpt = lp_ckpt.layered()
                    params = {"w_in": params["w1"], "b_in": params["b1"],
                              "mid": [],
                              "w_out": params["w2"], "b_out": params["b2"]}
            else:
                extra_like = jax.eval_shape(opt.init,
                                            deep.abstract_params(lp_meta))
                params, lp_ckpt, _, opt_state = restore_population(
                    args.ckpt_dir, extra_like=extra_like, mesh=mesh,
                    extra_specs=lp_meta.opt_specs(opt))
            if lp_ckpt != lp and lp_ckpt != lp.shard_pad(pop_axis_size(mesh)):
                print("note: resuming with the CHECKPOINT's layout "
                      f"({lp_ckpt.describe()})")
            lp = lp_ckpt
            if opt_state is None:
                # legacy checkpoint: no stored state; plain sgd's state is
                # just the step count, so a fresh init resumes exactly
                opt_state = jax.jit(
                    opt.init,
                    out_shardings=population_opt_shardings(lp, opt, mesh))(
                    params)
            print(f"resumed from step {last}"
                  + (f" (rung {rung}, {lp.num_real} survivors)"
                     if rung else ""))
        else:
            # shard-pad the layout to the population axis and initialise
            # born-sharded: the real members' params are BIT-IDENTICAL to a
            # single-device init (fillers draw from a folded key), and the
            # optimizer moments are born sharded alongside them (zeros —
            # identical padded or not).
            def born_sharded(key):
                p = deep.init_params(key, lp_real)
                return deep.pad_params(p, lp_real, lp,
                                       jax.random.fold_in(key, 1))
            params = jax.jit(
                born_sharded,
                out_shardings=population_shardings(lp, mesh))(
                jax.random.PRNGKey(args.seed))
            opt = build_opt(lp)
            opt_state = jax.jit(
                opt.init,
                out_shardings=population_opt_shardings(lp, opt, mesh))(
                params)
        print(f"population: {lp.describe()}  optimizer: {opt_name}"
              + (f" (grad clip {grad_clip})" if grad_clip else ""))

        # everything below depends on the RESOLVED layout (a resumed
        # checkpoint may change member count and feature/class dims)
        task = TabularTask(args.samples, lp.in_features,
                           n_classes=lp.out_features, seed=args.seed)
        (xtr, ytr), (xte, yte) = task.split()
        xte_j, yte_j = jnp.asarray(xte), jnp.asarray(yte)

        def lifecycle_meta():
            m = {"rung": rung, "n_members0": int(n0),
                 "member_ids": [int(i) for i in member_ids]}
            if refill_mode != "off":
                # refill state rides the lifecycle meta as extra keys (the
                # reader's .get() ignores them on old checkpoints): the id
                # counter, the lineage table, and the GROWN tails of the
                # per-member recipe vectors — a resume must reuse them, a
                # fresh draw would only cover the original n0
                m["next_id"] = int(next_id)
                m["lineage"] = {str(k): [int(p), int(b)]
                                for k, (p, b) in sorted(lineage.items())}
                if lr0 is not None:
                    m["lr_vec"] = [float(v) for v in lr0]
                if mom0 is not None:
                    m["mom_vec"] = [float(v) for v in mom0]
                if wd0 is not None:
                    m["wd_vec"] = [float(v) for v in wd0]
            return m

        train_meta = {"compute_dtype": args.compute_dtype,
                      "bd_impl": args.bd_impl,
                      "optimizer": opt_record,
                      "lr_schedule": args.lr_schedule}

        # ---- LR schedule (PR-6 follow-up): a per-step multiplier threaded
        # through the scanned chunk as a carried global-step counter, so a
        # chunked run anneals identically to a per-step loop and --resume
        # re-enters the schedule at the right step.  The schedule composes
        # with --per-member-lr (it scales the member vector uniformly).
        lr_sched = (warmup_cosine(1.0, args.warmup, args.steps)
                    if args.lr_schedule == "warmup_cosine" else None)

        total = args.steps
        print_every = max(50 // scan, 1)
        stats = report if report is not None else {}
        stats.update(restarts=0)
        pipeline = args.pipeline == "on"
        pf = None          # ONE Prefetcher for the run, retargeted per rung
        pending = []       # the in-flight (chunk, DeferredMetrics) (≤ 1)
        tracer = ChunkTrace(args.trace_dir)
        # chunk programs keyed (layout, optimizer epoch): a rung boundary
        # that changes neither — the constant-size refill — reuses the
        # SAME traced callable, so its jitted executable is a guaranteed
        # compile-cache hit (zero re-jit; DESIGN.md §13).  Shrinking rungs
        # change lp and build fresh entries, exactly the historical path.
        chunk_cache = {}

        def train_segment(params, opt_state, lp, opt, seg_start, seg_end):
            """Global steps [seg_start, seg_end) under the CURRENT layout:
            jitted donated scan chunks carrying (params, opt_state),
            batches device_put sharded over the 'data' axis, TrainRunner
            replay/checkpoints against the layout's own param AND opt spec
            trees (the state key is 'extra' to match
            ``save_population``/``restore_population``'s on-disk schema).

            With ``--pipeline on`` (default) the segment runs through the
            streaming data plane (data/pipeline.py, DESIGN.md §11): a
            producer thread builds chunk c+1's slab into alternating host
            staging and device_puts it (sharded over 'data') while chunk c
            executes, the slab is DONATED into the chunk, and each chunk's
            host metric fetch is DEFERRED until the next chunk is already
            dispatched — the device queue never drains at the host
            boundary.  The trajectory is bit-identical to ``--pipeline
            off`` (same chunk index → same slab; tests/test_pipeline.py)."""
            nonlocal pf
            lr = member_lr(lp)
            chunk_key = (lp, opt_epoch)
            chunk_fn = chunk_cache.get(chunk_key)
            if chunk_fn is None:
                chunk_fn = chunk_cache[chunk_key] = \
                    deep.make_population_train_step(
                        lp, optimizer=opt, grad_clip=grad_clip,
                        bd_impl=args.bd_impl, scan_steps=scan,
                        donate_batch=pipeline,
                        compute_dtype=args.compute_dtype,
                        lr_schedule=lr_sched)
                stats["chunk_builds"] = stats.get("chunk_builds", 0) + 1
            sh_x, sh_y = population_batch_shardings(mesh, args.batch)
            n_chunks = (seg_end - seg_start + scan - 1) // scan

            # one probe batch pins the staging dtypes/shapes (pure function
            # of the step index — building it twice changes nothing)
            bx0, by0 = task.batch(seg_start, args.batch)

            def make_staging():
                return (np.empty((scan,) + bx0.shape, bx0.dtype),
                        np.empty((scan,) + by0.shape, by0.dtype))

            def build_slab(c, staging):
                """Chunk c's (scan, B, ...) slab, staged on host and
                device_put sharded — the producer-thread body (also the
                synchronous path's builder, so both paths stage and copy
                identically).  The slab handed to device_put is a SNAPSHOT
                of the staging region: a sharded device_put of a numpy
                array may zero-copy ALIAS its memory (jax CPU backend
                does), so the reusable staging buffer itself must never
                become a device buffer — the snapshot is what the device
                owns, and nothing ever writes it again (DESIGN.md §11
                aliasing rule)."""
                sx, sy = staging
                g0 = seg_start + c * scan
                n = min(scan, seg_end - g0)
                task.batch_slab(g0, n, args.batch, out=(sx[:n], sy[:n]))
                return (jax.device_put(np.array(sx[:n]), sh_x),
                        jax.device_put(np.array(sy[:n]), sh_y))

            if pipeline:
                if pf is None:
                    pf = Prefetcher(build_slab, n_chunks,
                                    make_staging=make_staging,
                                    depth=args.prefetch_depth)
                else:
                    # rung-boundary flush: drop slabs staged for the OLD
                    # segment, re-aim the producer at this one — the
                    # signature lets retarget KEEP the staging buffers
                    # when the slab shapes are unchanged (every
                    # constant-population rung) instead of reallocating
                    sig = (((scan,) + bx0.shape, np.dtype(bx0.dtype).str),
                           ((scan,) + by0.shape, np.dtype(by0.dtype).str))
                    pf.retarget(build_slab, n_chunks,
                                make_staging=make_staging, signature=sig)
            sync_staging = None if pipeline else make_staging()

            def resolve_metrics(pers, gnorms, g0, n, c):
                """Host side of chunk c's metrics — runs at force() time,
                i.e. after chunk c+1 is dispatched (pipelined) or inline
                (sync).  Resolution happens in chunk order either way, so
                the stats and prints match the historical loop exactly."""
                def resolve():
                    # mean over REAL members only — shard-pad fillers train
                    # too but must not dilute the reported loss (a sharded
                    # run prints the same numbers as its single-device twin)
                    per = np.asarray(pers[:, :lp.num_real])
                    stats.setdefault("first_loss", float(per[0].mean()))
                    mean = float(per[-1].mean())
                    stats["last_loss"] = mean
                    stats["per_member_last"] = per[n - 1]
                    metrics = {"loss": mean, "step": g0 + n - 1}
                    if gnorms is not None:
                        # pre-clip global grad norm, one per inner step —
                        # the chunk's last one rides the metrics log
                        metrics["grad_norm"] = float(np.asarray(gnorms)[n - 1])
                    if c % print_every == 0:
                        gn = (f"  grad norm {metrics['grad_norm']:.3f}"
                              if gnorms is not None else "")
                        print(f"step {g0 + n - 1:4d}  mean member loss "
                              f"{mean:.4f}{gn}")
                    return metrics
                return resolve

            def step_fn(state, c):
                g0 = seg_start + c * scan
                n = min(scan, seg_end - g0)
                tracer.chunk_start(c, state)
                with StepTraceAnnotation("train_chunk", step_num=c):
                    xs, ys = (pf.get(c) if pipeline
                              else build_slab(c, sync_staging))
                    # with a schedule, the chunk takes the chunk-start
                    # GLOBAL step and carries it through the scan — g0 is
                    # derived from the segment, so crash replay and
                    # --resume stay consistent
                    sched_args = ((jnp.asarray(g0, jnp.int32),) if lr_sched
                                  else ())
                    t_call = time.perf_counter()
                    p, st, _losses, pers, gnorms = chunk_fn(
                        state["params"], state["extra"], xs, ys, lr,
                        *sched_args)
                # the first call traces and compiles before it dispatches
                stats.setdefault("compile_s", time.perf_counter() - t_call)
                dm = DeferredMetrics(resolve_metrics(pers, gnorms, g0, n, c))
                # pipelined: chunk c is dispatched; NOW pay chunk c-1's host
                # fetch while c runs (the final chunk resolves after run())
                pending.append((c, dm))
                resolve_pending(keep=1 if pipeline else 0)
                return {"params": p, "extra": st}, dm

            def resolve_pending(keep=0):
                while len(pending) > keep:
                    pc, pdm = pending.pop(0)
                    pdm.force(chunk=pc)

            def on_restore(c):
                # crash replay: metrics queued for the abandoned trajectory
                # must not resolve (their chunks re-run); the prefetcher
                # re-seeks itself on the out-of-order get(c)
                pending.clear()

            def chunk_crosses_cadence(c):
                # chunk c covers global steps [g0, g1): checkpoint iff one
                # of them completes a --ckpt-every multiple (the per-step
                # loop's "(step+1) % every == 0" cadence, quantized up to
                # chunk end)
                if not args.ckpt_every:
                    return False
                g0 = seg_start + c * scan
                g1 = min(g0 + scan, seg_end)
                return g1 // args.ckpt_every > g0 // args.ckpt_every

            runner = TrainRunner(
                step_fn, {"params": params, "extra": opt_state},
                ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every,
                straggler=StragglerPolicy(timeout_s=args.straggler_timeout),
                ckpt_meta=population_meta(lp, params,
                                          lifecycle=lifecycle_meta(),
                                          train_meta=train_meta),
                ckpt_step_map=lambda c: min(seg_start + (c + 1) * scan,
                                            seg_end) - 1,
                ckpt_step_unmap=lambda g: (g + 1 - seg_start) // scan - 1,
                ckpt_save_pred=chunk_crosses_cadence,
                on_restore=on_restore,
                mesh=mesh, state_specs={"params": lp.param_specs(),
                                        "extra": lp.opt_specs(opt)})
            try:
                runner.run(n_chunks)
                # the segment's last chunk still owes its host fetch —
                # resolve it before the rung boundary / final eval reads
                # stats
                resolve_pending()
            finally:
                tracer.stop()
            stats["restarts"] += runner.restarts
            if pf is not None:
                print("prefetch: " + " ".join(
                    f"{k} {v:.4f}" if isinstance(v, float) else f"{k} {v}"
                    for k, v in pf.stats.items()))
            # planned work, counted once per segment (a crash-replayed
            # chunk must not inflate the reported throughput)
            stats["member_steps"] = (stats.get("member_steps", 0)
                                     + lp.num_real * (seg_end - seg_start))
            return runner.state["params"], runner.state["extra"]

        def rewarm_adafactor_state(fresh, carried, lp_real, lp, opt):
            """Merge the carried params-shaped momentum + step count into a
            freshly initialised (born-sharded, all-zero) adafactor state on
            the padded layout.  The factored v_row/v_col stay at the fresh
            zeros — they reduce over the fused hidden axis, so survivors'
            statistics mix members and cannot be gathered; zeroing them
            costs the ~1/(1−b2)-step re-warm documented on --halving."""
            if carried["m"] is None:
                return {**fresh, "count": carried["count"]}
            m_pad = deep.pad_state(carried["m"], lp_real, lp)
            is_state_leaf = lambda x: isinstance(x, dict) and (
                "v" in x or "v_row" in x)
            o_sh = population_opt_shardings(lp, opt, mesh)
            m_pad = jax.device_put(
                m_pad, jax.tree.map(lambda sh: sh["m"], o_sh["leaves"],
                                    is_leaf=is_state_leaf))
            leaves = jax.tree.map(lambda st, m: {**st, "m": m},
                                  fresh["leaves"], m_pad,
                                  is_leaf=is_state_leaf)
            return {"count": jax.device_put(carried["count"],
                                            o_sh["count"]),
                    "leaves": leaves}

        server = None

        def publish_live(params, lp):
            """The PR-7 leftover driver hook: refresh the serving
            leaderboard from the LIVE run so the published member set
            tracks the halving ladder (rung boundaries + final state)."""
            nonlocal server
            from repro.launch.serve_population import PopulationServer
            n_cal = xte_j.shape[0]
            if args.rung_eval_batches:
                n_cal = min(n_cal, args.rung_eval_batches * args.batch)
            if server is None:
                server = PopulationServer(
                    params, lp, mesh=mesh, bd_impl=args.bd_impl,
                    batch=args.batch,
                    topk=min(4, lp.num_real))
            else:
                server.refresh(params, lp)
            server.publish(xte_j[:n_cal], yte_j[:n_cal])
            print(f"published: best1={server.published['best1']} "
                  f"topk={server.published['topk']}")
            return server

        # rung segments: [0, b0) prune [b0, b1) prune ... [b_last, total).
        # A resumed run re-enters the ladder at its checkpointed rung (the
        # boundaries before it are already applied to the layout).
        segments = schedule.segments(total) if schedule else ((total, None),)
        t0 = time.time()
        pos = start
        try:
            for i in range(min(rung, len(segments) - 1) if schedule else 0,
                           len(segments)):
                seg_end, keep_frac = segments[i]
                if pos < seg_end:
                    params, opt_state = train_segment(params, opt_state, lp,
                                                      opt, pos, seg_end)
                    pos = seg_end
                if keep_frac is None:
                    continue
                # ---- rung boundary: eval under the training sharding (on a
                # subsampled split when --rung-eval-batches asks for cheap
                # rungs — halving only needs rank fidelity at the cut line),
                # prune, compact PARAMS AND OPTIMIZER MOMENTS into a freshly
                # bucketed layout ON DEVICE (jitted static-index gather, no
                # host round-trip), re-pad to the mesh (zero filler moments),
                # device_put born-sharded; the next segment re-jits against the
                # physically smaller population with a rebuilt optimizer whose
                # per-member hyper trees follow the survivor mapping.
                n_eval = xte_j.shape[0]
                if args.rung_eval_batches:
                    n_eval = min(n_eval, args.rung_eval_batches * args.batch)
                losses, _ = evaluate_population(params, lp, xte_j[:n_eval],
                                                yte_j[:n_eval])
                n_before = lp.num_real
                rung_losses = np.asarray(losses)[:n_before]
                keep = survivors(rung_losses, keep_frac)
                rung = i + 1
                plan = None
                if controller is not None:
                    plan = controller.plan(
                        lp, rung_losses, keep, member_ids, rung=rung,
                        next_id=next_id, base_lr=arch.lr,
                        lr=None if lr0 is None else lr0[member_ids],
                        momentum=None if mom0 is None else mom0[member_ids],
                        wd=None if wd0 is None else wd0[member_ids],
                        base_momentum=args.momentum,
                        base_wd=args.weight_decay)
                    # refilled recipes append at their FRESH ids (plan
                    # order == id order), never overwriting a pruned
                    # member's entry — survivors' rows are untouched, so
                    # the no-refill prefix of every vector stays bit-exact
                    for f in plan.members:
                        lineage[f.member_id] = (f.parent_id, f.birth_rung)
                        if lr0 is not None:
                            lr0 = np.append(lr0, np.asarray(f.lr,
                                                            lr0.dtype))
                        if mom0 is not None:
                            mom0 = np.append(mom0, np.asarray(f.momentum,
                                                              mom0.dtype))
                        if wd0 is not None:
                            wd0 = np.append(wd0, np.asarray(f.wd,
                                                            wd0.dtype))
                    next_id += len(plan.members)
                    stats["refilled"] = (stats.get("refilled", 0)
                                         + len(plan.members))
                if refill_mode == "pbt":
                    # ---- constant-size refill: population size is held
                    # (prune k → refill k into the SAME slots), so the
                    # post-rung layout is IDENTICAL — no compact, no
                    # re-shard-pad, no device_put migration.  The boundary
                    # is one jitted on-device gather/scatter (exploit
                    # clones + fresh inits), a moment mask-zero, and a
                    # recipe rewrite; lr is a runtime chunk argument, so
                    # an lr-only mutation re-enters the SAME compiled
                    # chunk (zero re-jit, asserted via the chunk cache).
                    fresh = None
                    fm = plan.fresh_members
                    if fm:
                        fresh_lp = LayeredPopulation(
                            lp.in_features, lp.out_features,
                            tuple(f.widths for f in fm),
                            tuple(f.acts for f in fm), block=lp.block)
                        fresh = deep.init_params(
                            jax.random.fold_in(
                                jax.random.PRNGKey(args.seed),
                                5000 + rung), fresh_lp)
                    params = refill_params(lp, params, plan.assignments,
                                           fresh, gather="device")
                    opt_state = refill_state(opt_state, lp, plan.slots)
                    member_ids = member_ids.copy()
                    for f in plan.members:
                        member_ids[f.slot] = f.member_id
                    if mom0 is not None or wd0 is not None:
                        # baked momentum/decay trees changed → the chunk
                        # re-specializes (the documented cost of mutating
                        # trace-time recipe constants; lr-only runs skip
                        # this entirely)
                        opt = build_opt(lp)
                    hit = (lp, opt_epoch) in chunk_cache
                    n_ex = sum(1 for f in plan.members
                               if f.origin == "exploit")
                    print(f"rung {i} @ step {pos - 1}: pruned "
                          f"{n_before - len(keep)}/{n_before}, refilled in "
                          f"place ({n_ex} exploit, "
                          f"{len(plan.members) - n_ex} fresh) -> layout "
                          f"unchanged, chunk "
                          + ("cache-hit (zero re-jit)" if hit
                             else "rebuild"))
                else:
                    kept_ids = member_ids[keep]
                    if opt_name == "adafactor":
                        # factored second moments cannot ride the
                        # member-major gather — carry momentum + count,
                        # re-init v_row/v_col
                        lp_real, params_keep, fac_carry = compact_factored(
                            lp, params, opt_state, keep)
                        opt_keep = None
                    else:
                        lp_real, params_keep, opt_keep = compact(
                            lp, params, opt_state, keep)
                    member_ids = kept_ids
                    if refill_mode == "arch":
                        # ---- grow-layout refill: freshly sampled
                        # architectures splice into the compacted layout
                        # (the inverse of compact — survivors bit-exact,
                        # newborns fresh-init, zero moments), then the
                        # grown layout re-pads and re-jits as any
                        # shape-changing rung does.
                        widths_new = tuple(f.widths for f in plan.members)
                        acts_new = tuple(f.acts for f in plan.members)
                        positions = lp_real.grow_positions(widths_new,
                                                           acts_new)
                        lp_grown = lp_real.grow(widths_new, acts_new,
                                                positions)
                        fresh_lp = lp_grown.subset(tuple(sorted(positions)))
                        fresh = deep.init_params(
                            jax.random.fold_in(
                                jax.random.PRNGKey(args.seed),
                                5000 + rung), fresh_lp)
                        params_keep = grow_params(lp_real, lp_grown,
                                                  params_keep, positions,
                                                  fresh)
                        if opt_keep is not None:
                            opt_keep = deep.grow_state(opt_keep, lp_real,
                                                       lp_grown, positions)
                        elif fac_carry["m"] is not None:
                            mdt = jax.tree.leaves(fac_carry["m"])[0].dtype
                            zeros = jax.tree.map(
                                lambda s: jnp.zeros(s.shape, mdt),
                                deep.abstract_params(fresh_lp))
                            fac_carry = {**fac_carry, "m": grow_params(
                                lp_real, lp_grown, fac_carry["m"],
                                positions, zeros)}
                        new_ids = np.empty(lp_grown.num_real,
                                           member_ids.dtype)
                        pos_of = {p: j for j, p in enumerate(positions)}
                        oi = 0
                        for slot in range(lp_grown.num_real):
                            if slot in pos_of:
                                new_ids[slot] = \
                                    plan.members[pos_of[slot]].member_id
                            else:
                                new_ids[slot] = member_ids[oi]
                                oi += 1
                        member_ids = new_ids
                        lp_real = lp_grown
                    lp = lp_real.shard_pad(pop_axis_size(mesh))
                    fill = jax.random.fold_in(jax.random.PRNGKey(args.seed),
                                              1000 + rung)
                    params = jax.device_put(
                        deep.pad_params(params_keep, lp_real, lp, fill),
                        population_shardings(lp, mesh))
                    opt = build_opt(lp)
                    if opt_name == "adafactor":
                        fresh = jax.jit(
                            opt.init,
                            out_shardings=population_opt_shardings(
                                lp, opt, mesh))(params)
                        opt_state = rewarm_adafactor_state(fresh, fac_carry,
                                                           lp_real, lp, opt)
                    else:
                        opt_state = jax.device_put(
                            deep.pad_state(opt_keep, lp_real, lp),
                            population_opt_shardings(lp, opt, mesh))
                    if refill_mode == "arch":
                        print(f"rung {i} @ step {pos - 1}: kept "
                              f"{len(keep)}/{n_before}, grew "
                              f"{len(plan.members)} sampled archs -> "
                              f"{lp.describe()}")
                    else:
                        print(f"rung {i} @ step {pos - 1}: kept "
                              f"{len(keep)}/{n_before} members -> "
                              f"{lp.describe()}")
                if args.ckpt_every:
                    # force-save the COMPACTED state at the last COMPLETED step
                    # (pos-1 == the boundary step, except for catch-up prunes on
                    # a resume that was already past it), overwriting any
                    # cadence save of that step: the latest checkpoint always
                    # matches the live layout, so replay and --resume land on
                    # the new rung
                    save_population(args.ckpt_dir, pos - 1, params, lp,
                                    extra_state=opt_state,
                                    lifecycle=lifecycle_meta(),
                                    train_meta=train_meta)
                if args.serve_publish:
                    publish_live(params, lp)
        finally:
            if pf is not None:
                pf.close()
        dt = time.time() - t0

        steps_run = max(total - start, 0)
        if steps_run:
            loss0 = stats.get("first_loss", 0.0)
            loss = stats.get("last_loss", 0.0)
            member_steps = stats.get("member_steps",
                                     lp.num_real * steps_run)
            pop_desc = (f"{n0}->{lp.num_real}" if lp.num_real != n0
                        else f"{lp.num_real}")
            print(f"trained {pop_desc} MLPs × {steps_run} steps in "
                  f"{dt:.1f}s ({member_steps / max(dt, 1e-9):.0f} "
                  f"model-steps/s); loss {loss0:.4f} -> {loss:.4f}")
            if refill_mode != "off":
                # every id ever issued is a distinct model the search
                # visited — models explored per second
                print(f"explored {next_id} models "
                      f"({stats.get('refilled', 0)} refilled) in {dt:.1f}s "
                      f"({next_id / max(dt, 1e-9):.2f} models/s); "
                      f"{stats.get('chunk_builds', 0)} chunk builds")
            if args.ckpt_every:
                # final checkpoint ONLY if the cadence didn't just write it
                # (steps % ckpt_every == 0 used to save the last step twice)
                saved = latest_steps(args.ckpt_dir)
                if not saved or saved[-1] != total - 1:
                    save_population(args.ckpt_dir, total - 1, params, lp,
                                    extra_state=opt_state,
                                    lifecycle=lifecycle_meta(),
                                    train_meta=train_meta)

        if args.serve_publish:
            # final refresh: the served set always matches the state the
            # run ended on (rung boundaries already published mid-ladder)
            publish_live(params, lp)

        losses, accs = evaluate_population(params, lp, xte_j, yte_j)
        print("leaderboard:")
        for row in leaderboard(lp, losses, accs, k=min(10, lp.num_real),
                               member_ids=member_ids,
                               lineage=lineage if refill_mode != "off"
                               else None):
            lin = ""
            if "lineage" in row:
                li = row["lineage"]
                lin = (f"  born r{li['born_rung']}"
                       + (f" of {li['parent']}" if li["parent"] >= 0
                          else " fresh" if li["born_rung"] else " seed"))
            print(f"  #{row['rank']:2d} member {row['member']:4d} "
                  f"hidden={row['hidden']} {row['activation']:11s} "
                  f"loss={row['loss']:.4f} acc={row['acc']:.3f}{lin}")
        return params, lp


def main(argv=None, report=None, mesh=None):
    """Parse ``argv`` and train.  Population archs return ``(params,
    layout)``; ``report`` and ``mesh`` pass through to
    :func:`run_population`."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="laptop-scale family config (smoke/CI)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--num-micro", type=int, default=1)
    ap.add_argument("--grad-clip", type=float, default=None,
                    help="global-norm gradient clip; LM default 1.0, "
                         "population default OFF (0 disables; when set, "
                         "the pre-clip norm is logged per step)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--straggler-timeout", type=float, default=1e9)
    # population-engine flags (kind == "population")
    ap.add_argument("--population-depths", default=None,
                    help='heterogeneous-depth spec, e.g. "64,32,16;13,5;7" '
                         "(members by ';', per-layer widths by ',')")
    ap.add_argument("--population-acts", default="relu",
                    help="comma list cycled over members, or 'paper' for "
                         "the ten paper activations")
    ap.add_argument("--population-repeats", type=int, default=1)
    ap.add_argument("--population-features", type=int, default=20)
    ap.add_argument("--population-classes", type=int, default=2)
    ap.add_argument("--population-block", type=int, default=8)
    ap.add_argument("--samples", type=int, default=2048)
    ap.add_argument("--bd-impl", default="einsum", choices=BD_CHOICES,
                    help="the one implementation decision: XLA ops "
                         "(einsum) or the FUSED Pallas kernels (projection "
                         "+ bias + activation in one pass per layer, "
                         "DESIGN.md §7)")
    ap.add_argument("--compute-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="mixed-precision policy: matmul operands in this "
                         "dtype, f32 accumulators/params/loss/eval "
                         "(DESIGN.md §7)")
    ap.add_argument("--rung-eval-batches", type=int, default=0,
                    help="halving rungs: evaluate only this many --batch-"
                         "sized eval batches at each rung boundary (0 = "
                         "full split; the FINAL leaderboard eval always "
                         "runs the full split) — successive halving only "
                         "needs rank fidelity at the cut line")
    ap.add_argument("--scan-steps", type=int, default=8,
                    help="population path: optimizer steps fused into one "
                         "jitted lax.scan chunk (donated params, one "
                         "dispatch per chunk)")
    ap.add_argument("--pipeline", default="on", choices=["on", "off"],
                    help="population path: the streaming data plane "
                         "(DESIGN.md §11) — a producer thread stages the "
                         "NEXT chunk's batch slab into alternating host "
                         "buffers and device_puts it (sharded over 'data', "
                         "donated into the chunk) while the current chunk "
                         "runs, with per-chunk metric fetches deferred "
                         "until the next chunk is dispatched.  "
                         "Bit-identical trajectory to 'off' (the "
                         "synchronous build-then-dispatch loop)")
    ap.add_argument("--trace-dir", default=None,
                    help="population path: write a profiler trace of "
                         "chunks 1-3 of the first segment (after the "
                         "compile) under DIR: the train_chunk steps, the "
                         "data plane's prefetch.build / prefetch.wait / "
                         "metrics.resolve spans and the named Pallas "
                         "kernels on one clock")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="--pipeline on: producer queue bound — how many "
                         "chunks the data plane may run ahead before "
                         "backpressure blocks it (2 = double buffering)")
    ap.add_argument("--serve-publish", action="store_true",
                    help="population path: refresh a PopulationServer "
                         "leaderboard (launch/serve_population.py) from "
                         "the LIVE run at every halving rung boundary and "
                         "after the final step, so the published member "
                         "set tracks the ladder")
    ap.add_argument("--per-member-lr", action="store_true",
                    help="paper §7: every member gets its own step size")
    ap.add_argument("--lr-schedule", default="constant",
                    choices=["constant", "warmup_cosine"],
                    help="population path: per-step LR multiplier threaded "
                         "through the scanned chunk as a carried global-"
                         "step counter (warmup over --warmup steps, cosine "
                         "decay to 10%% over --steps).  Composes with "
                         "--per-member-lr; 'constant' keeps the historical "
                         "schedule-free chunk bit-exact")
    ap.add_argument("--optimizer", default=None,
                    choices=["sgd", "momentum", "adamw", "adafactor"],
                    help="population path: the stateful-optimizer engine "
                         "(DESIGN.md §8).  sgd = the paper's plain SGD "
                         "(stateless, bit-exact vs the historical step); "
                         "momentum = SGD + heavy-ball momentum; "
                         "adamw / adafactor as in repro.optim.  Optimizer "
                         "state is born sharded, compacted through halving "
                         "rungs, checkpointed, and validated on --resume. "
                         "Default: the arch's optimizer (sgd for "
                         "parallelmlp)")
    ap.add_argument("--momentum", type=float, default=0.9,
                    help="--optimizer momentum: heavy-ball coefficient")
    ap.add_argument("--weight-decay", type=float, default=0.0,
                    help="--optimizer adamw/adafactor: decoupled weight "
                         "decay (population default 0 — the paper's task "
                         "has no regularisation)")
    ap.add_argument("--opt-state-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="--optimizer adamw: moment (m/v) storage dtype — "
                         "bfloat16 halves optimizer HBM; moment MATH stays "
                         "f32 either way (DESIGN.md §8)")
    ap.add_argument("--per-member-momentum", action="store_true",
                    help="--optimizer momentum: sample one heavy-ball "
                         "coefficient per member (uniform [0.5, 0.99], "
                         "drawn once over the original population like "
                         "--per-member-lr)")
    ap.add_argument("--per-member-weight-decay", action="store_true",
                    help="--optimizer adamw/adafactor: sample one decay "
                         "per member (log-uniform around --weight-decay)")
    ap.add_argument("--halving", default=None,
                    help='successive-halving rungs "STEP:KEEP,..." (e.g. '
                         '"500:0.5,1000:0.5,2000:0.25"): after each listed '
                         "global step, keep the best fraction of surviving "
                         "members and COMPACT the fused layout (rungs at or "
                         "past --steps never fire; resume with the same "
                         "spec to continue a ladder mid-run).  With "
                         "--optimizer adafactor, the factored v_row/v_col "
                         "statistics are re-initialised to zero per member "
                         "at each rung boundary (they reduce over the "
                         "fused hidden axis and cannot be gathered "
                         "member-major); momentum and the step count carry "
                         "over, and the second moment re-warms in "
                         "~1/(1-b2) steps (~100 at the default b2=0.99)")
    ap.add_argument("--refill", default="off",
                    choices=["off", "pbt", "arch"],
                    help="slot-refill search at --halving rung boundaries "
                         "(DESIGN.md §13): after pruning, refill the freed "
                         "slots instead of shrinking.  'pbt' holds the "
                         "population size constant — exploit/explore clones "
                         "of same-arch survivors with perturbed recipes "
                         "(fresh init when no arch matches); the layout "
                         "never changes, so the rung boundary is one "
                         "on-device gather/scatter with ZERO re-jit.  "
                         "'arch' samples fresh architectures from "
                         "--search-space and GROWS the layout (inverse of "
                         "compaction).  'off' (default) is the historical "
                         "halving driver, bit-identical")
    ap.add_argument("--search-space", default=None,
                    help="declarative search-space spec for --refill, "
                         "';'-separated, e.g. \"widths=64,32|16,8;"
                         "acts=relu,tanh;lr=0.3..3;momentum=0.5..0.99;"
                         "wd=0.3..3;lr_perturb=0.8,1.25;"
                         "momentum_jitter=0.05\".  Unset keys keep the "
                         "defaults, which reproduce the historical "
                         "hardcoded per-member ranges bit-for-bit")
    ap.add_argument("--refill-exploit-frac", type=float, default=0.5,
                    help="--refill pbt: truncation-selection fraction — "
                         "exploit clones draw uniformly from the best "
                         "FRAC of the slot-arch-matching survivors")
    args = ap.parse_args(argv)

    configure_compile_cache()
    arch = get_arch(args.arch, reduced=args.reduced)
    if arch.kind == "population":
        return run_population(arch, args, report=report, mesh=mesh)
    if mesh is None:
        mesh = make_host_mesh()
    print(f"arch={args.arch} mesh={dict(mesh.shape)} "
          f"devices={len(jax.devices())}")
    if arch.kind in ("lm", "encdec"):
        run_lm(arch, args, mesh)
    else:
        raise SystemExit(f"unknown arch kind {arch.kind!r}")


if __name__ == "__main__":
    main()
