"""Fault tolerance: checkpoint/restart loop, straggler watchdog, elastic
re-mesh.

At 1000+ nodes the mean time between chip/host failures drops below job
length; the framework therefore treats the train loop as a RESUMABLE pure
function of (checkpoint, step, data(step)):

  * ``TrainRunner`` — drives steps, checkpoints asynchronously every K
    steps, and on ANY exception prints it, restores the last committed
    checkpoint and replays (data is step-indexed → bitwise-identical
    replay: the restored state lands on the shardings the live state had
    at that step, so the replay runs the same executables).  Failure
    injection hooks make this testable on one host
    (tests/test_fault_tolerance.py).
  * ``StragglerPolicy`` — wall-clock per-step watchdog.  On a real pod the
    reaction is implemented by the control plane (preempt + re-slice); in
    this single-process framework the policy records the event, optionally
    triggers an elastic re-mesh, and raises after ``max_strikes``
    consecutive slow steps so the runner's restart path takes over.
  * ``elastic_remesh`` — rebuild a mesh from the CURRENTLY live device set
    (after losing a pod or scaling in new ones) and re-shard a state tree
    onto it.  Works because checkpoints store full host arrays and the
    spec trees are mesh-shape-agnostic (sharding.filter_spec).
"""
from __future__ import annotations

import dataclasses
import sys
import time
import traceback
from typing import Callable, Optional

import jax
import numpy as np

from repro.checkpoint import AsyncCheckpointer, latest_steps, restore
from repro.distributed.sharding import logical_to_sharding


@dataclasses.dataclass
class StragglerPolicy:
    timeout_s: float = 60.0
    max_strikes: int = 3
    on_straggler: Optional[Callable[[int, float], None]] = None
    strikes: int = 0
    events: list = dataclasses.field(default_factory=list)

    def observe(self, step: int, dt: float):
        if dt <= self.timeout_s:
            self.strikes = 0
            return
        self.strikes += 1
        self.events.append((step, dt))
        if self.on_straggler:
            self.on_straggler(step, dt)
        if self.strikes >= self.max_strikes:
            raise TimeoutError(
                f"step {step}: {self.strikes} consecutive steps over "
                f"{self.timeout_s}s — requesting restart/re-slice")


def elastic_remesh(state_tree, spec_tree, axis_order=("data", "model"),
                   devices=None):
    """Rebuild the largest (data × model) mesh from live devices and
    re-shard ``state_tree`` onto it.  model dim is kept if possible,
    data absorbs the remainder (data parallelism degrades gracefully)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    model = 1
    for cand in (16, 8, 4, 2, 1):
        if n % cand == 0:
            model = cand
            break
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((n // model, model), axis_order,
                     devices=np.asarray(devices))
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state_tree)
    sh = logical_to_sharding(spec_tree, mesh, abstract)
    resharded = jax.tree.map(
        lambda x, s: jax.device_put(np.asarray(jax.device_get(x)), s),
        state_tree, sh)
    return mesh, resharded


def _shardings_of(tree):
    """The sharding tree of a state whose leaves are all jax Arrays, else
    None (host or scalar leaves place by default)."""
    leaves = jax.tree.leaves(tree)
    if not leaves or not all(isinstance(x, jax.Array) for x in leaves):
        return None
    return jax.tree.map(lambda x: x.sharding, tree)


class TrainRunner:
    """Checkpoint/restart training driver.

    step_fn(state, step) -> (state, metrics)  must be pure & replayable.
    ``failure_hook(step)`` (tests) may raise to simulate chip loss."""

    def __init__(self, step_fn, state, *, ckpt_dir: str,
                 ckpt_every: int = 50, keep_last: int = 3,
                 straggler: StragglerPolicy | None = None,
                 failure_hook: Optional[Callable[[int], None]] = None,
                 max_restarts: int = 3, ckpt_meta: dict | None = None,
                 ckpt_step_map: Optional[Callable[[int], int]] = None,
                 ckpt_step_unmap: Optional[Callable[[int], int]] = None,
                 ckpt_save_pred: Optional[Callable[[int], bool]] = None,
                 on_restore: Optional[Callable[[int], None]] = None,
                 restore_shardings=None, mesh=None, state_specs=None):
        """``ckpt_meta``/``ckpt_step_map``: forwarded to the checkpointer
        (population runs attach the fused layout and record GLOBAL step
        numbers while the runner counts scan chunks); ``ckpt_step_unmap``
        is the inverse of ``ckpt_step_map`` — the crash-restore path maps a
        restored checkpoint's recorded step back into the runner's step
        domain.  ``restore_shardings``: optional sharding tree matching
        ``state`` — crash restores device_put straight back onto the mesh
        instead of replicating.  ``mesh`` + ``state_specs`` (a
        PartitionSpec tree matching ``state``, e.g. ``{"params":
        layout.param_specs()}``) derive ``restore_shardings`` here, so
        callers wire their LOGICAL specs through and mid-run replay stays
        sharded without hand-building NamedSharding trees.

        ``on_restore(step)`` fires after every crash restore with the step
        the replay will re-enter at — the hook for re-synchronising
        step-indexed side state the replay would otherwise desynchronise
        (the streaming data plane drops queued slabs / unresolved deferred
        metrics for the abandoned trajectory, DESIGN.md §11)."""
        self.step_fn = step_fn
        self.state = state
        if restore_shardings is None and mesh is not None \
                and state_specs is not None:
            abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
            restore_shardings = logical_to_sharding(state_specs, mesh,
                                                    abstract)
        self.ckpt = AsyncCheckpointer(ckpt_dir, every=ckpt_every,
                                      keep_last=keep_last, meta=ckpt_meta,
                                      step_map=ckpt_step_map,
                                      save_pred=ckpt_save_pred)
        self.ckpt_step_unmap = ckpt_step_unmap or (lambda s: s)
        self.on_restore = on_restore
        self.restore_shardings = restore_shardings
        self.straggler = straggler or StragglerPolicy(timeout_s=1e9)
        self.failure_hook = failure_hook
        self.max_restarts = max_restarts
        self.restarts = 0
        self.metrics_log = []
        # host snapshot of the INITIAL state: a failure before the first
        # committed checkpoint replays from step 0 (data is step-indexed, so
        # replay is exact) — required because the current live state may
        # have been mutated by completed steps or DELETED by an
        # argument-donating step that failed mid-chunk.  Skipped when the
        # directory already holds a committed checkpoint (resume: _restore
        # reads disk instead) and freed as soon as one commits.
        self._init_state_host = None if latest_steps(ckpt_dir) else \
            jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state)
        # shardings of the initial state and of the state after the last
        # completed step: a replay restores onto them, so the restored
        # state re-enters the jitted step with the SAME input shardings as
        # the uninterrupted run (a spec-derived sharding that is equivalent
        # but spelled differently keys a second executable, whose reduction
        # order may differ in the last ulp)
        self._init_shardings = _shardings_of(state)
        self._live_shardings = self._init_shardings

    def _put(self, host_tree, shardings=None):
        shardings = shardings or self.restore_shardings
        if shardings is not None:
            return jax.tree.map(jax.device_put, host_tree, shardings)
        return jax.tree.map(jax.device_put, host_tree)

    def _restore(self):
        self.ckpt.wait()
        steps = latest_steps(self.ckpt.directory)
        if not steps:
            if self._init_state_host is None:
                # can only happen if the checkpoint dir vanished after a
                # commit freed the snapshot — nothing left to replay from
                raise RuntimeError(
                    f"no committed checkpoint under {self.ckpt.directory} "
                    "and the initial-state snapshot was already released")
            self.state = self._put(self._init_state_host,
                                   self._init_shardings)
            self._live_shardings = self._init_shardings
            if self.on_restore:
                self.on_restore(0)
            return 0
        self.state, step = restore(
            self.ckpt.directory, self.state,
            shardings=self._live_shardings or self.restore_shardings)
        step = self.ckpt_step_unmap(step) + 1
        if self.on_restore:
            self.on_restore(step)
        return step

    def run(self, num_steps: int, start_step: int = 0) -> int:
        step = start_step
        while step < num_steps:
            try:
                t0 = time.time()
                if self.failure_hook:
                    self.failure_hook(step)
                self.state, metrics = self.step_fn(self.state, step)
                self.straggler.observe(step, time.time() - t0)
                self.metrics_log.append((step, metrics))
                self._live_shardings = _shardings_of(self.state)
                self.ckpt.maybe_save(step, self.state)
                if self._init_state_host is not None and self.ckpt.saved:
                    self._init_state_host = None  # a checkpoint committed
                step += 1
            except (KeyboardInterrupt,):
                raise
            except Exception as e:   # noqa: BLE001 — restart on ANY failure
                self.restarts += 1
                print(f"TrainRunner: step {step} failed (restart "
                      f"{self.restarts}/{self.max_restarts}): "
                      + "".join(traceback.format_exception_only(e)).strip(),
                      file=sys.stderr, flush=True)
                if self.restarts > self.max_restarts:
                    raise RuntimeError(
                        f"exceeded {self.max_restarts} restarts") from e
                step = self._restore()
        self.ckpt.wait()
        return step
