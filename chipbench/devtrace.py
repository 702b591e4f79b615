"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run on the chip.  The harness's own spans
(``jax.profiler.TraceAnnotation``) are events of the host plane's threads,
on the same clock.  Everything is clipped to the harness's ``window``
span.
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "window"
# the harness's spans that an idle gap can be charged to
HOST_SPANS = ("stage_slab", "input_wait", "dispatch_chunk", "fetch_metrics",
              "drain")


def op_name(event_name: str) -> str:
    """``%fusion.90 = f32[...] fusion(...)`` -> ``fusion.90``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _leaves(evs):
    """Drop operations that contain others (a scan's ``while``): what is
    left are the operations that did the work, none overlapping."""
    evs = sorted(evs, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (n, s, e) in enumerate(evs):
        if i + 1 < len(evs) and evs[i + 1][1] < e and evs[i + 1][2] <= e:
            continue
        out.append((n, s, e))
    return out


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    """``ops[device]``: ``(name, start_ns, end_ns)`` of every device
    operation inside the window, containers left out; ``spans``: ``(name, start_ns, end_ns)``
    of the host spans; ``window``: ``(start_ns, end_ns)``."""

    def __init__(self, ops: dict, spans: list, window: tuple):
        self.window = window
        self.spans = spans
        w0, w1 = window
        self.ops = {d: [(n, max(s, w0), min(e, w1)) for n, s, e in
                        _leaves(evs) if e > w0 and s < w1]
                    for d, evs in ops.items()}

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        ops, spans = {}, []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                evs = ops.setdefault(int(m.group(1)), [])
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        evs.extend((op_name(e.name), e.start_ns,
                                    e.start_ns + e.duration_ns)
                                   for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans.extend((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events
                                 if e.name in HOST_SPANS + (WINDOW,))
        windows = [(s, e) for n, s, e in spans if n == WINDOW]
        if len(windows) != 1:
            raise ValueError(f"trace holds {len(windows)} '{WINDOW}' spans")
        return cls(ops, spans, windows[0])

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, device) -> float:
        return sum(e - s for s, e in _union(
            (s, e) for _, s, e in self.ops[device])) * 1e-9

    def idle_gaps(self, device) -> list:
        """``(start_ns, end_ns)`` of every interval of the window in which
        no operation ran on ``device``."""
        gaps, t = [], self.window[0]
        for s, e in _union((s, e) for _, s, e in self.ops[device]):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            gaps.append((t, self.window[1]))
        return gaps

    def op_seconds(self, device, patterns=None) -> dict:
        """Device seconds per operation name, of the names that contain
        one of ``patterns`` (all names when None)."""
        out = {}
        for n, s, e in self.ops[device]:
            if patterns is None or any(p in n for p in patterns):
                out[n] = out.get(n, 0.0) + (e - s) * 1e-9
        return out

    def host_activity(self, start: int, end: int) -> str:
        """The harness span that overlaps ``[start, end)`` most."""
        best, name = 0, "no_span"
        for n, s, e in self.spans:
            if n == WINDOW:
                continue
            ov = min(e, end) - max(s, start)
            if ov > best:
                best, name = ov, n
        return name

    def breakdown(self, top: int = 10) -> dict:
        devices = sorted(self.ops)
        tot = {}
        for d in devices:
            for n, sec in self.op_seconds(d).items():
                tot[n] = tot.get(n, 0.0) + sec / len(devices)
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(((e - s, s, e) for d in devices
                       for s, e in self.idle_gaps(d)), reverse=True)[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[self.host_activity(s, e), dur * 1e-9]
                              for dur, s, e in gaps]}
