"""One cell: a configuration under a traffic mix on its chips, driven
through the program's training hot loop.

Set-up builds the program's scanned, donated chunk
(``core.deep.make_population_train_step``), makes the parameters on the
device from the seed (``members.ProgramLayout.pack``) under the shardings
the chunk hands its state back in, starts the program's ``Prefetcher``
over the benchmark's data, and runs the first chunk through the window's
own call and feed: those first steps are what the reference checks.  The
window then runs the training loop of ``launch/train.py``
(``train_segment``, without checkpoints): take the next slab, dispatch the
chunk, fetch the previous chunk's metrics, until the time is up, ending on
``block_until_ready``.  The first chunk and every chunk of the window run
one executable: the chunk's jit cache holds one entry, and nothing
compiles in the window.
"""
from __future__ import annotations

import gc
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

import data
import members as mb
import reference

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class CompileCounter:
    """Counts traces and backend compiles while ``on``."""

    def __init__(self):
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name in COMPILE_EVENTS:
            self.count += 1


def load(bench_dir: pathlib.Path, bench: dict, name: str) -> dict:
    """The cell's ``BENCHMARK.json`` entry with its configuration, traffic
    and limits read from their files."""
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")

    def read(sub, stem):
        return json.loads((bench_dir / sub / f"{stem}.json").read_text())

    return {**entry, "cfg": read("configs", entry["config"]),
            "traffic_mix": read("traffic", entry["traffic"]),
            "limits": read("workloads", name)["limits"]}


class Cell:
    def __init__(self, spec: dict, devices: list):
        from repro.core import deep
        from repro.core.population import LayeredPopulation
        from repro.distributed.sharding import (population_batch_shardings,
                                                population_opt_shardings,
                                                population_shardings)
        from repro.launch.mesh import make_mesh
        from repro.optim import adamw, sgd

        self.cfg = cfg = spec["cfg"]
        self.mix = mix = spec["traffic_mix"]
        self.chips = len(devices)
        self.members = mb.expand(cfg)
        self.batch, self.scan = mix["batch"], mix["scan_steps"]
        self.mesh = make_mesh((1, self.chips), ("data", "model"),
                              devices=devices)
        lp = LayeredPopulation(
            cfg["in_features"], cfg["classes"],
            tuple(w for w, _ in self.members),
            tuple((a,) * len(w) for w, a in self.members),
            block=cfg["block"]).sorted().shard_pad(self.chips)
        self.lp = lp
        self.layout = mb.ProgramLayout(lp, self.members)
        o = cfg["optimizer"]
        if o["name"] == "sgd":
            self.opt = sgd()
        elif o["name"] == "adamw":
            self.opt = adamw(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                             weight_decay=o["weight_decay"],
                             state_dtype=jnp.float32)
        else:
            raise ValueError(f"unknown optimizer {o['name']!r}")
        self.lr = float(o["lr"])
        impl = {k: cfg[k] for k in ("bd_impl",) if k in cfg}
        self.chunk = deep.make_population_train_step(
            lp, optimizer=self.opt, scan_steps=self.scan, donate_batch=True,
            **impl)
        self.param_sh = population_shardings(lp, self.mesh)
        self.opt_sh = population_opt_shardings(lp, self.opt, self.mesh)
        self.sh_x, self.sh_y = population_batch_shardings(self.mesh,
                                                          self.batch)
        self.ix = jax.tree.map(
            lambda a: np.asarray(a, np.int32 if a.dtype != bool else bool),
            self.layout.index_arrays())
        self.segs = [np.asarray(s, np.int32) for s in self.layout.segments()]
        per_chip = lp.num_members // self.chips
        chip = np.arange(lp.num_members) // per_chip
        self.chip_of = np.zeros(len(self.members), np.int64)
        real = self.layout.canon >= 0
        self.chip_of[self.layout.canon[real]] = chip[real]
        self.compiles = CompileCounter()
        self._state_sh = None

    # ------------------------------------------------------------------ #
    def abstract_args(self) -> tuple:
        """The chunk's arguments as shapes under the program's shardings."""
        from repro.core import deep

        def sds(a, sh):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

        params = jax.tree.map(sds, deep.abstract_params(self.lp),
                              self.param_sh)
        state = jax.tree.map(sds, jax.eval_shape(self.opt.init, params),
                             self.opt_sh)
        f = self.lp.in_features
        xs = jax.ShapeDtypeStruct((self.scan, self.batch, f), jnp.float32,
                                  sharding=self.sh_x)
        ys = jax.ShapeDtypeStruct((self.scan, self.batch), jnp.int32,
                                  sharding=self.sh_y)
        return params, state, xs, ys, self.lr

    def state_shardings(self):
        """The shardings the chunk returns its parameters and optimizer
        state in.  State made under the packed shardings would run a first
        executable of its own, and hand the window a second."""
        if self._state_sh is None:
            out = self.chunk.lower(
                *self.abstract_args()).compile().output_shardings
            self._state_sh = (out[0], out[1])
        return self._state_sh

    def setup(self, seed: int, wrap=None):
        """Parameters, optimizer state, the data feed, and the first chunk,
        with the program's readings of it.  ``wrap(chunk, cell)`` stands in
        for the program's chunk where given (the tests plant faults through
        it)."""
        from repro.data import Prefetcher
        self.seed = seed
        self.key = mb.seed_words(seed)
        self.task = data.Task(self.cfg["samples"], self.cfg["in_features"],
                              self.cfg["classes"], seed)
        call = wrap(self.chunk, self) if wrap else self.chunk
        self.call = call
        self.phases = {}
        t = time.perf_counter()
        param_sh, opt_sh = self.state_shardings()
        self.phases["shardings"] = time.perf_counter() - t
        t = time.perf_counter()
        params = jax.jit(self.layout.pack, out_shardings=param_sh)(
            self.key, self.ix)
        opt_state = jax.jit(self.opt.init, out_shardings=opt_sh)(params)
        jax.block_until_ready(opt_state)
        self.phases["weights"] = time.perf_counter() - t
        f = self.cfg["in_features"]

        def make_staging():
            return (np.empty((self.scan, self.batch, f), np.float32),
                    np.empty((self.scan, self.batch), np.int32))

        def build_slab(c, staging):
            with TraceAnnotation("stage_slab"):
                sx, sy = self.task.slab(c * self.scan, self.scan, self.batch,
                                        out=staging)
                return (jax.device_put(np.array(sx), self.sh_x),
                        jax.device_put(np.array(sy), self.sh_y))

        self.pf = Prefetcher(build_slab, 1 << 40, make_staging=make_staging)
        t = time.perf_counter()
        xs, ys = self.pf.get(0)
        params, opt_state, losses, pers, _ = call(params, opt_state, xs, ys,
                                                  self.lr)
        jax.block_until_ready(losses)
        self.phases["chunk0"] = time.perf_counter() - t
        t = time.perf_counter()
        self.prog = self._readings(params, opt_state, losses, pers)
        self.phases["readings"] = time.perf_counter() - t
        self.state = (params, opt_state)
        self.last = (losses, pers)

    def _readings(self, params, opt_state, losses, pers) -> dict:
        """The program's first chunk in canonical member order."""
        layout, segs = self.layout, self.segs

        def change(p, key, ix, segs):
            p0 = layout.pack(key, ix)
            return layout.member_sumsq(jax.tree.map(jnp.subtract, p, p0),
                                       segs)

        canon = layout.canon
        real = canon >= 0

        def to_canon(v):
            v = np.asarray(v, np.float64)
            out = np.zeros(v.shape[:-1] + (len(self.members),))
            out[..., canon[real]] = v[..., real]
            return out

        out = {"losses": np.asarray(losses, np.float64),
               "pers": to_canon(pers),
               "change": {k: to_canon(v) for k, v in jax.device_get(
                   jax.jit(change)(params, self.key, self.ix, segs)).items()}}
        if "m" in opt_state:
            out["moment"] = {k: to_canon(v) for k, v in jax.device_get(
                jax.jit(layout.member_sumsq)(opt_state["m"], segs)).items()}
        return out

    # ------------------------------------------------------------------ #
    def window(self, seconds: float) -> dict:
        """The timed loop.  Returns chunks, steps, seconds, the host time
        spent waiting for slabs, non-finite chunks, compiles, and the
        executables the chunk's jit holds."""
        from repro.data import DeferredMetrics
        params, opt_state = self.state
        self.state = None
        pending = DeferredMetrics(lambda: {"losses": np.asarray(self.last[0])})
        c, wait, bad = 1, 0.0, 0
        # the set-up's objects, JAX's among them, out of the collector's
        # reach: a full collection over them stalls the loop for tens of ms
        gc.collect()
        gc.freeze()
        self.compiles.on = True
        t0 = time.perf_counter()
        with TraceAnnotation("window"):
            while True:
                with TraceAnnotation("input_wait"):
                    tw = time.perf_counter()
                    xs, ys = self.pf.get(c)
                    wait += time.perf_counter() - tw
                with TraceAnnotation("dispatch_chunk"):
                    params, opt_state, losses, pers, _ = self.call(
                        params, opt_state, xs, ys, self.lr)
                with TraceAnnotation("fetch_metrics"):
                    bad += not np.isfinite(pending["losses"]).all()
                pending = DeferredMetrics(
                    lambda losses=losses, pers=pers: {
                        "losses": np.asarray(losses),
                        "pers": np.asarray(pers)})
                c += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            with TraceAnnotation("drain"):
                jax.block_until_ready((params, opt_state))
                bad += not np.isfinite(pending["losses"]).all()
        t1 = time.perf_counter()
        self.compiles.on = False
        gc.unfreeze()
        self.state = (params, opt_state)
        chunks = c - 1
        return {"chunks": chunks, "steps": chunks * self.scan,
                "seconds": t1 - t0, "input_wait_s": wait,
                "nonfinite_chunks": bad, "compiles": self.compiles.count,
                "chunk_executables": self.chunk._cache_size()}

    def free(self):
        self.pf.close()
        self.state = self.last = None

    # ------------------------------------------------------------------ #
    def check(self, precision: str = "highest", batch_rows=None) -> dict:
        """Reference over the first chunk's slab, and the numbers compared.
        ``batch_rows`` trains the reference on the first rows of each batch
        only (the half-batch fault read against a sound reference)."""
        xs, ys = self.task.slab(0, self.scan, self.batch)
        if batch_rows is not None:
            xs, ys = xs[:, :batch_rows], ys[:, :batch_rows]
        ref = reference.run(self.cfg, self.members, self.key, xs, ys,
                            precision=precision,
                            device=self.mesh.devices.flat[0])
        return ref

    def numbers(self, ref: dict, prog: dict | None = None) -> dict:
        return reference.numbers(prog or self.prog, ref, self.chip_of,
                                 self.chips,
                                 self.cfg["optimizer"]["name"] == "adamw")

    @property
    def real_members(self) -> int:
        return self.lp.num_real
