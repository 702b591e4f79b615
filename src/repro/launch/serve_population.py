"""Population serving engine: batched ensemble inference (DESIGN.md §10).

``python -m repro.launch.serve_population --ckpt-dir /tmp/pop_ck_fused``

The population counterpart of ``launch/serve.py``'s prefill/decode driver.
Request lifecycle:

  1. requests land in a HOST staging buffer (two of them, alternating, so
     requests for flush k+1 stage while flush k's device slab is in flight);
  2. the buffer flushes to device when it fills to ``batch`` — or when the
     max-latency timer for its oldest request fires first (a partial slab,
     zero-padded to keep the jit cache at one entry per mode);
  3. ONE jitted step per ensemble mode runs the forward-only fused path
     (``deep.forward(infer=True)``: depth+1 launches, no residuals, the
     request slab DONATED so XLA reuses its device buffer across flushes)
     and reduces the (B, P, O) member outputs on device
     (``core.ensemble``): best-member routing, top-k soft-vote, or
     all-members soft-vote, each with disagreement uncertainty;
  4. per-request latency = flush wait + step wall; the driver reports
     p50/p99 and req/s per mode (``--json-out`` rows).

The served member set comes from ``selection.leaderboard`` over a
calibration split evaluated with the SAME infer-path kernels
(``publish``): rank-0 becomes ``best1``'s route, the top-k slots become
``topk``'s vote — refreshing it mid-training at rung boundaries is just
calling ``publish`` again.  Shard-pad fillers can never be published or
reduced over (``core.ensemble`` validates; regression in
tests/test_infer_path.py).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import set_mesh

from repro.core.deep import BD_CHOICES
from repro.core.ensemble import ENSEMBLE_MODES, ensemble_predict, real_slots
from repro.core.selection import evaluate_population, leaderboard
from repro.launch.launch_count import (count_pallas_launches,
                                       fused_infer_budget, max_eqn_outputs)


class PopulationServer:
    """Batched ensemble serving over a trained (possibly sharded)
    population.  ``modes``: any of ``("best1", "topk", "all")``."""

    def __init__(self, params, layout, *, mesh=None, bd_impl: str = "fused",
                 compute_dtype=None,
                 weights_dtype=None, batch: int = 32, topk: int = 4,
                 max_latency_ms: float = 5.0):
        self.params = params
        self.layout = layout
        self.mesh = mesh
        self.batch = int(batch)
        self.topk = int(topk)
        self.max_latency_ms = float(max_latency_ms)
        self.weights_dtype = weights_dtype
        self._fw = dict(bd_impl=bd_impl, compute_dtype=compute_dtype,
                        infer=True)
        if weights_dtype is not None:
            self._fw["weights_dtype"] = weights_dtype
        # int8 serve copy (DESIGN.md §12): built lazily, once, from the
        # restored/refreshed master weights — after that the server holds
        # ONLY the quantized tree (the f32 masters are released)
        self._quantized = weights_dtype is None
        # donated double buffers: two host staging slabs alternate so the
        # next flush stages while the previous device slab is in flight,
        # and the device copy is donated into the jitted step
        self._host = [np.zeros((self.batch, layout.in_features), np.float32)
                      for _ in range(2)]
        self._flip = 0
        self._steps: dict[str, object] = {}
        self.board = None
        self.published: dict = {"all": None}

    # ----------------------------------------------------------------- #
    # published member set                                              #
    # ----------------------------------------------------------------- #

    def refresh(self, params, layout):
        """Re-target the server at a LIVE training run's current state —
        the rung-boundary driver hook (launch/train.py --serve-publish).
        Halving compaction changes the layout (member count, fused width),
        so everything keyed on it resets: the per-mode jit cache (layouts
        are jit constants), the leaderboard and published sets (old member
        slots no longer exist), and the host staging slabs if the feature
        width changed.  Call :meth:`publish` after to re-derive the served
        member set on the new population."""
        if layout.in_features != self.layout.in_features:
            self._host = [
                np.zeros((self.batch, layout.in_features), np.float32)
                for _ in range(2)]
        self.params = params
        self.layout = layout
        # a halving rung may shrink the population below the served top-k
        self.topk = max(1, min(self.topk, real_slots(layout)))
        self._steps.clear()
        self.board = None
        self.published = {"all": None}
        self._quantized = self.weights_dtype is None   # re-quantize fresh
        return self

    def _ensure_quantized(self):
        """Replace the master weights with the int8 serve copy, once per
        refresh — every consumer of ``self.params`` (publish, the per-mode
        steps, check_budget) funnels through here, so after the first call
        the server never holds an f32/bf16 weight copy again."""
        if self._quantized:
            return
        from repro.quant import quantize_population
        self.params = jax.block_until_ready(
            jax.jit(quantize_population, static_argnums=1)(
                self.params, self.layout))
        self._quantized = True

    def publish(self, x_calib, y_calib, task: str = "classification",
                sort_by: str = "loss"):
        """Refresh the served member set from a leaderboard over a
        calibration split — scored with the SAME forward-only kernels the
        serve steps run (under ``weights_dtype="int8"`` that includes the
        fused-dequant kernels, so the board ranks what is actually
        served).  Returns the leaderboard rows."""
        self._ensure_quantized()
        losses, accs = evaluate_population(
            self.params, self.layout, x_calib, y_calib, task=task,
            **self._fw)
        k = max(self.topk, 1)
        self.board = leaderboard(self.layout, losses, accs, k=k,
                                 sort_by=sort_by)
        self.published = {
            "best1": [self.board[0]["slot"]],
            "topk": [r["slot"] for r in self.board[:self.topk]],
            "all": None,                  # every real member, sliced on device
        }
        self._steps.clear()               # member sets are jit constants
        return self.board

    # ----------------------------------------------------------------- #
    # jitted per-mode step                                              #
    # ----------------------------------------------------------------- #

    def _step(self, mode: str):
        if mode not in ENSEMBLE_MODES:
            raise ValueError(f"unknown mode {mode!r} (have {ENSEMBLE_MODES})")
        if mode not in self._steps:
            if mode != "all" and mode not in self.published:
                raise ValueError(f"mode {mode!r} needs a published member "
                                 "set — call publish() first")
            self._ensure_quantized()
            ids = self.published.get(mode)
            lp, fw = self.layout, self._fw

            def step(params, xb):
                from repro.core.deep import forward
                logits = forward(params, xb, lp, **fw)
                return ensemble_predict(logits, lp, mode, member_ids=ids,
                                        with_uncertainty=True)

            self._steps[mode] = jax.jit(step, donate_argnums=(1,))
        return self._steps[mode]

    # ----------------------------------------------------------------- #
    # request loop                                                      #
    # ----------------------------------------------------------------- #

    def run(self, xs, mode: str = "all", warmup: bool = True) -> dict:
        """Serve ``xs`` (N, F) through the batching loop → per-request
        predictions + latency stats.  Closed-loop: all requests are queued
        at t=0, so full slabs flush on fill and only the trailing partial
        slab flushes on its max-latency timer (its requests pay that wait
        in their recorded latency).  ``warmup`` runs one zero slab before
        the clock starts so p50/p99 measure serving, not compilation."""
        step = self._step(mode)
        n = int(xs.shape[0])
        xs = np.asarray(xs, np.float32)
        lat = np.zeros(n)
        preds = np.zeros(n, np.int64)
        probs = np.zeros((n, self.layout.out_features), np.float32)
        unc = np.zeros(n, np.float32)
        if warmup:
            jax.block_until_ready(step(
                self.params,
                jnp.zeros((self.batch, self.layout.in_features),
                          jnp.float32))["pred"])
        t0 = time.perf_counter()
        i = 0
        while i < n:
            nb = min(self.batch, n - i)
            buf = self._host[self._flip]
            self._flip ^= 1
            buf[:nb] = xs[i:i + nb]
            if nb < self.batch:               # max-latency flush: timer fired
                buf[nb:] = 0.0
            out = step(self.params, jnp.asarray(buf))
            pred = np.asarray(
                jax.block_until_ready(out["pred"]))[:nb]
            probs[i:i + nb] = np.asarray(out["probs"])[:nb]
            mi = np.asarray(out["mutual_information"])[:nb]
            done = time.perf_counter() - t0
            # every request in the slab completes at the flush's done time;
            # a timer-fired partial slab waited out max_latency first
            lat[i:i + nb] = done + (self.max_latency_ms / 1e3
                                    if nb < self.batch else 0.0)
            preds[i:i + nb] = pred
            unc[i:i + nb] = mi
            i += nb
        wall = time.perf_counter() - t0
        return {
            "mode": mode,
            "members_served": (real_slots(self.layout)
                               if self.published.get(mode) is None
                               else len(self.published[mode])),
            "requests": n,
            "pred": preds,
            "probs": probs,
            "mutual_information": unc,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "req_per_s": n / max(wall, 1e-9),
            "wall_s": wall,
        }

    # ----------------------------------------------------------------- #
    # invariants                                                        #
    # ----------------------------------------------------------------- #

    def check_budget(self):
        """Loud-fail §10 invariants on the traced serve forward: exactly
        depth+1 Pallas launches and every one single-output (no residual
        buffers can exist in a serving program)."""
        self._ensure_quantized()
        lp, fw = self.layout, self._fw
        xb = jnp.zeros((self.batch, lp.in_features), jnp.float32)

        def fwd(params):
            from repro.core.deep import forward
            return forward(params, xb, lp, **fw)

        budget = fused_infer_budget(lp.depth)
        got = count_pallas_launches(fwd, self.params)
        if got != budget["total"]:
            raise SystemExit(f"serve forward dispatches {got} launches, "
                             f"budget is {budget['total']} (depth+1)")
        worst = max_eqn_outputs(fwd, self.params)
        if worst > 1:
            raise SystemExit(f"serve forward emits a {worst}-output "
                             "pallas_call — a residual buffer survived")
        return {"launches": got, "budget": budget["total"]}

    @classmethod
    def from_checkpoint(cls, ckpt_dir: str, step: int | None = None,
                        mesh=None, **kw):
        from repro.checkpoint.checkpoint import restore_population
        params, layout, step = restore_population(ckpt_dir, step=step,
                                                  mesh=mesh)
        return cls(params, layout, mesh=mesh, **kw), step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--step", type=int, default=None)
    ap.add_argument("--modes", nargs="+", default=list(ENSEMBLE_MODES),
                    choices=list(ENSEMBLE_MODES))
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--topk", type=int, default=4)
    ap.add_argument("--max-latency-ms", type=float, default=5.0)
    ap.add_argument("--calib-samples", type=int, default=512)
    ap.add_argument("--sharded", action="store_true",
                    help="restore + serve on the host mesh (population "
                    "axis sharded across devices)")
    ap.add_argument("--bd-impl", default="fused", choices=BD_CHOICES)
    ap.add_argument("--compute-dtype", default=None)
    ap.add_argument("--weights-dtype", default=None, choices=["int8"],
                    help="int8: quantize the restored weights once "
                    "(quant.quantize_population) and serve ONLY the int8 "
                    "copy through the fused-dequant kernels — ~4x params "
                    "HBM vs f32 (DESIGN.md §12)")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)

    from repro.launch.cache import configure_compile_cache
    configure_compile_cache()
    mesh = None
    if args.sharded:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh()
    server, step = PopulationServer.from_checkpoint(
        args.ckpt_dir, step=args.step, mesh=mesh, batch=args.batch,
        topk=args.topk, max_latency_ms=args.max_latency_ms,
        bd_impl=args.bd_impl, compute_dtype=args.compute_dtype,
        weights_dtype=args.weights_dtype)
    lp = server.layout
    print(f"restored step {step}: {real_slots(lp)} members "
          f"(+{lp.num_members - real_slots(lp)} fillers), "
          f"F={lp.in_features} O={lp.out_features} depth={lp.depth}")

    from repro.data.synthetic import TabularTask
    task = TabularTask(args.calib_samples + args.requests, lp.in_features,
                       n_classes=lp.out_features, seed=0)
    (xc, yc), (xr, _) = task.split(
        frac=args.calib_samples / (args.calib_samples + args.requests))

    with (set_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        if args.bd_impl == "fused":
            print("launch budget:", server.check_budget())
        board = server.publish(xc, yc)
        print(f"published: best1={server.published['best1']} "
              f"topk={server.published['topk']}")
        for row in board[:3]:
            print("  ", row)
        results = {}
        for mode in args.modes:
            r = server.run(xr[:args.requests], mode)
            results[mode] = {k: v for k, v in r.items()
                             if k not in ("pred", "probs",
                                          "mutual_information")}
            print(f"{mode:6s} members={r['members_served']:3d} "
                  f"p50={r['p50_ms']:.2f}ms p99={r['p99_ms']:.2f}ms "
                  f"{r['req_per_s']:.0f} req/s")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"step": step, "board": board, "serve": results}, f,
                      indent=2, default=str)
        print("wrote", args.json_out)


if __name__ == "__main__":
    main()
