"""Streaming data plane: double-buffered async host->device prefetch
(DESIGN.md §11).

The population train loop is device-bound arithmetic wrapped in host-bound
glue: every scan chunk waits while the driver generates batches, stacks
them, and ``device_put``s the slab, and every per-chunk metric fetch
(``np.asarray`` on per-member losses / grad norms) drains the dispatch
pipeline before the next chunk can launch.  "On the Performance of Network
Parallel Training in Artificial Neural Networks" (PAPERS.md) measures
exactly this failure mode — data movement, not FLOPs, bounding parallel
ANN training.  This module closes the seam with two pieces:

  * :class:`Prefetcher` — a background producer thread that materialises
    the NEXT chunk's ``(scan_steps, B, ...)`` batch slab into one of two
    alternating host staging buffers and ``device_put``s it (sharded by
    ``distributed.sharding.population_batch_shardings``) while the current
    chunk executes on device.  The promoted, reusable form of the
    double-buffer pattern ``launch.serve_population.PopulationServer``
    already uses for request slabs.  A bounded queue (default depth 2 —
    double buffering) gives backpressure; ``seek`` re-synchronises after a
    crash replay; ``retarget`` flushes and re-aims the producer when a
    halving rung boundary re-shard-pads the layout and re-jits the chunk;
    ``close`` shuts the thread down even when it is blocked mid-``put``.
    Producer exceptions are captured and re-raised on the consumer thread
    (``get``) — a dead producer can never hang the train loop.

  * :class:`DeferredMetrics` — a chunk's metrics as a lazy mapping over
    the live device arrays: the host transfer happens on FIRST ACCESS, so
    the driver resolves chunk N's metrics after chunk N+1 is already
    dispatched and the device queue never drains for a ``float()``.

Bit-exactness contract: the prefetcher changes WHEN a batch is built and
copied, never WHAT is built — ``produce(chunk_idx, staging)`` is required
to be a pure function of the chunk index (the repo's step-indexed data
rule), so a pipelined run's trajectory is bit-identical to the synchronous
driver's (tests/test_pipeline.py).

Both pieces trace themselves on the profiler's clock, with no switch: an
annotation costs about a microsecond when no profiler listens.  Each span
carries ``chunk=c``, so a chunk's producer span and the loop's spans for
it line up in one trace:

  * ``prefetch.build`` — the producer's ``produce(c, staging)`` call
    (host fill plus ``device_put``), on the producer thread;
  * ``prefetch.wait`` — the blocking part of :meth:`Prefetcher.get`, opened
    only when the queue is empty, i.e. when the consumer is starved;
  * ``metrics.resolve`` — :meth:`DeferredMetrics.force`'s first resolve,
    the host fetch of a chunk's metrics.

``Prefetcher.stats`` counts the same boundaries in plain Python numbers:
``built``/``build_s`` (producer), ``got``, ``starved``/``wait_s``
(consumer)."""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterator, Mapping, Optional

from jax.profiler import TraceAnnotation


class PrefetchError(RuntimeError):
    """Producer-thread failure, re-raised on the consumer thread with the
    original exception chained (``raise ... from err``)."""


def staging_signature(staging):
    """Shape/dtype signature of a staging buffer — nested tuples mirroring
    the buffer's structure with each numpy array replaced by
    ``(shape, dtype.str)``.  This is the equality key
    :meth:`Prefetcher.retarget` uses to decide whether the existing
    staging buffers can be REUSED across a rung boundary (constant-
    population refill keeps every slab shape identical) instead of being
    discarded and reallocated; callers that know the next segment's shapes
    can build the signature by hand without allocating anything."""
    if staging is None:
        return None
    if isinstance(staging, (tuple, list)):
        return tuple(staging_signature(s) for s in staging)
    if not (hasattr(staging, "shape") and hasattr(staging, "dtype")):
        # non-array leaf (e.g. a test double): opaque by type — never
        # claims shape equality, so retarget falls back to a rebuild
        return ("opaque", type(staging).__name__)
    import numpy as np
    return (tuple(staging.shape), np.dtype(staging.dtype).str)


class DeferredMetrics(Mapping):
    """A metrics dict whose values stay on device until first access.

    ``resolve()`` is called once, lazily; its result (a plain dict) is
    cached.  Everything mapping-like (``metrics["loss"]``, ``dict(m)``,
    iteration, ``len``) forces resolution — so code that stores the object
    (``TrainRunner.metrics_log``) costs nothing, and code that reads it
    pays one host sync at read time, ideally after the NEXT chunk is in
    flight."""

    __slots__ = ("_resolve", "_value")

    def __init__(self, resolve: Callable[[], dict]):
        self._resolve = resolve
        self._value: Optional[dict] = None

    @property
    def resolved(self) -> bool:
        return self._value is not None

    def force(self, chunk: Optional[int] = None) -> dict:
        """The resolved dict; the first call fetches it inside a
        ``metrics.resolve`` span, tagged with ``chunk`` when given."""
        if self._value is None:
            with TraceAnnotation("metrics.resolve",
                                 **({} if chunk is None else
                                    {"chunk": chunk})):
                self._value = dict(self._resolve())
        return self._value

    def __getitem__(self, key):
        return self.force()[key]

    def __iter__(self) -> Iterator:
        return iter(self.force())

    def __len__(self) -> int:
        return len(self.force())

    def __repr__(self) -> str:
        if self._value is None:
            return "DeferredMetrics(<unresolved>)"
        return f"DeferredMetrics({self._value!r})"


class Prefetcher:
    """Bounded async producer of per-chunk device slabs.

    Parameters
    ----------
    produce : ``(chunk_idx, staging) -> slab``
        Runs ON THE PRODUCER THREAD.  Builds chunk ``chunk_idx``'s batches
        into ``staging`` (one of two alternating host buffers from
        ``make_staging``, or ``None``) and returns the device slab —
        typically the ``jax.device_put(..., sharding)`` of the staged
        arrays.  Must be a pure function of ``chunk_idx`` (step-indexed
        data) so replays and the synchronous path agree bit-for-bit.
    n_chunks : total chunks in the current target (exclusive end).
    make_staging : optional zero-arg factory for ONE host staging buffer;
        called twice so consecutive chunks alternate buffers — chunk k+1
        stages while chunk k's device slab is still in flight.  ALIASING
        RULE: a sharded ``jax.device_put`` of a numpy array may ZERO-COPY
        alias its memory (the jax CPU backend does), so ``produce`` must
        never hand a staging buffer itself to the device — snapshot the
        staged region (``np.array``) and device_put the snapshot, which
        nothing ever writes again (DESIGN.md §11).
    depth : queue bound (default 2 = double buffering): the producer runs
        at most ``depth`` chunks ahead, then blocks (backpressure) until
        the consumer drains one.

    ``stats`` counts what the data plane did since construction: slabs
    ``built`` and the producer's ``build_s`` inside ``produce``; slabs
    ``got`` by the consumer, the gets that found the queue empty
    (``starved``) and the consumer's ``wait_s`` blocked in them.
    """

    _END = object()

    def __init__(self, produce: Callable[[int, Any], Any], n_chunks: int,
                 *, make_staging: Optional[Callable[[], Any]] = None,
                 depth: int = 2, start: int = 0, name: str = "prefetch"):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._depth = depth
        self._name = name
        self._produce = produce
        self._make_staging = make_staging
        self._staging = ([make_staging(), make_staging()]
                         if make_staging else [None, None])
        self._signature = staging_signature(self._staging[0])
        self._n_chunks = int(n_chunks)
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._next = int(start)          # next chunk the consumer expects
        # each count has one writer: the producer thread (built, build_s)
        # or the consumer (got, starved, wait_s)
        self.stats = {"built": 0, "got": 0, "starved": 0, "build_s": 0.0,
                      "wait_s": 0.0}
        self._start_thread(int(start))

    # ----------------------------------------------------------------- #
    # producer                                                          #
    # ----------------------------------------------------------------- #

    def _start_thread(self, start: int):
        self._stop.clear()
        self._error = None
        self._q = queue.Queue(maxsize=self._depth)
        self._thread = threading.Thread(
            target=self._run, args=(start,), daemon=True, name=self._name)
        self._thread.start()

    def _run(self, start: int):
        flip = 0
        try:
            for c in range(start, self._n_chunks):
                if self._stop.is_set():
                    return
                t = time.perf_counter()
                with TraceAnnotation("prefetch.build", chunk=c):
                    slab = self._produce(c, self._staging[flip])
                self.stats["build_s"] += time.perf_counter() - t
                self.stats["built"] += 1
                flip ^= 1
                if not self._put((c, slab)):
                    return
            self._put(self._END)
        except BaseException as e:       # noqa: BLE001 — surface on get()
            self._error = e
            self._put(self._END)

    def _put(self, item) -> bool:
        """Bounded put with condition-variable backpressure: a blocked
        producer parks on the queue's internal ``not_full`` condition and
        wakes IMMEDIATELY when the consumer ``get``s a slab (no polling
        interval — tests assert <10 ms).  ``close``/``retarget`` unblock a
        full-queue put the same way: ``_halt`` sets the stop flag and then
        drains the queue, each drained item notifying ``not_full``; the
        post-wake stop check discards the stale hand-off (the queue object
        is rebuilt on restart, so a raced-in item can never leak into the
        next target's stream)."""
        if self._stop.is_set():
            return False
        self._q.put(item)
        return not self._stop.is_set()

    # ----------------------------------------------------------------- #
    # consumer                                                          #
    # ----------------------------------------------------------------- #

    def get(self, chunk_idx: int, timeout: float = 600.0):
        """The device slab for ``chunk_idx``.  Consecutive calls must walk
        the chunk range in order; an out-of-order index (a crash replay
        restarting mid-segment, or a resume skipping ahead) triggers an
        implicit :meth:`seek` — queued slabs for the abandoned position are
        discarded and the producer restarts at ``chunk_idx``."""
        if chunk_idx != self._next:
            self.seek(chunk_idx)
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                item = self._wait(chunk_idx, timeout)
            if item is self._END:
                if self._error is not None:
                    self._raise()
                raise PrefetchError(
                    f"{self._name}: chunk {chunk_idx} requested past the "
                    f"end of the target ({self._n_chunks} chunks)")
            c, slab = item
            if c != chunk_idx:           # stale slab from before a seek
                continue
            self._next = chunk_idx + 1
            self.stats["got"] += 1
            return slab

    def _wait(self, chunk_idx: int, timeout: float):
        """Block for the queue's next item: the consumer is starved."""
        self.stats["starved"] += 1
        t = time.perf_counter()
        deadline = timeout
        try:
            with TraceAnnotation("prefetch.wait", chunk=chunk_idx):
                while True:
                    try:
                        return self._q.get(timeout=min(deadline, 0.5))
                    except queue.Empty:
                        deadline -= 0.5
                    if self._error is not None:
                        self._raise()
                    if not self._thread.is_alive():
                        raise PrefetchError(
                            f"{self._name}: producer thread died without "
                            f"delivering chunk {chunk_idx}")
                    if deadline <= 0:
                        raise TimeoutError(
                            f"{self._name}: chunk {chunk_idx} not produced "
                            f"within {timeout}s")
        finally:
            self.stats["wait_s"] += time.perf_counter() - t

    def _raise(self):
        err = self._error
        raise PrefetchError(
            f"{self._name}: producer thread failed while building a "
            f"batch slab: {err!r}") from err

    def seek(self, chunk_idx: int):
        """Flush and restart the producer at ``chunk_idx`` (crash-replay
        re-synchronisation: ``TrainRunner`` restores a checkpoint and the
        loop re-enters at an earlier chunk)."""
        self._halt()
        self._next = int(chunk_idx)
        self._start_thread(int(chunk_idx))

    def retarget(self, produce: Callable[[int, Any], Any], n_chunks: int,
                 *, make_staging: Optional[Callable[[], Any]] = None,
                 signature=None, start: int = 0):
        """Flush the pipeline and aim it at a NEW chunk source — the rung-
        boundary protocol: in-flight slabs for the old segment are always
        dropped and the producer restarts against the next segment's
        ``produce`` (chunk indices re-base on the new segment, so a stale
        slab can never be served), but the STAGING buffers are reused when
        ``signature`` (:func:`staging_signature` of the next segment's
        buffers, buildable from shapes alone) matches the current one —
        the constant-population refill keeps every slab shape identical
        across the rung, so no host buffer is discarded or reallocated
        there.  A shrinking rung changes the signature and takes the full
        rebuild path as before; omitting ``signature`` while passing
        ``make_staging`` also forces the rebuild (the conservative
        pre-refill behaviour)."""
        self._halt()
        self._produce = produce
        self._n_chunks = int(n_chunks)
        if make_staging is not None:
            self._make_staging = make_staging
            if signature is None or signature != self._signature:
                self._staging = [make_staging(), make_staging()]
                self._signature = staging_signature(self._staging[0])
        self._next = int(start)
        self._start_thread(int(start))

    def _halt(self):
        """Stop the producer thread and drain the queue (dropping slabs)."""
        self._stop.set()
        while True:                      # unblock a producer stuck in put()
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            if self._thread.is_alive():  # pragma: no cover — defensive
                raise RuntimeError(
                    f"{self._name}: producer thread failed to stop")
        self._thread = None

    def close(self):
        """Shut the producer down; idempotent, never hangs (``_halt``'s
        queue drain wakes a producer blocked in ``put`` via the queue's
        ``not_full`` condition, and the producer re-checks the stop flag
        after every wake)."""
        if self._thread is not None:
            self._halt()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
