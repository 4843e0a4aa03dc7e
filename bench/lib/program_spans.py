"""The program's own spans in a profiler trace (``.xplane.pb``).

While a JAX profiler session collects, every span of ``repro.obs`` opens a
``jax.profiler.TraceAnnotation`` named ``repro.<span>``, with the span's
keyword arguments (``bytes``, ``what``) as its metadata.  They lie on the
host plane, on the device planes' clock.  This module reads them inside the
``bench.window`` annotation, parsing each file once for every reader:

* seconds, self seconds (less the time their direct child spans cover) and
  count for each span name;
* sums of each numeric argument for each span name;
* device-idle seconds by the innermost ``repro.`` span over each idle
  instant, the busy union computed as ``trace.py`` computes it.  An idle
  interval is split where the innermost span changes: one gap often spans
  several host phases (a Newton step's whole solve is one), so booking it
  whole to the span over its midpoint, as ``trace.py`` does for the
  benchmark's own spans, would credit a phase with idle time longer than
  the phase itself.

A trace with no ``repro.`` span in the window (a program without them)
reads as ``None``.  Nothing here imports ``repro``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import heapq
import os

from bench.lib.trace import DEVICE_PLANE, WINDOW, _clip, _union, find_xplane

PREFIX = "repro."
# spans that only hold other spans: idle time under one of them, and not
# under a phase inside it, is not attributed to any phase
CONTAINERS = frozenset({
    "analyze", "replan", "factorize", "solve", "fixpoint", "fixpoint_chunk",
    "factor_level", "factor_segment", "solve_forward", "solve_backward",
    "refine"})
NONE = "none"                 # idle time under no repro span at all


@dataclasses.dataclass
class ProgramSpans:
    n_devices: int
    window_s: float
    busy_s: list                 # per device, inside the window
    seconds: dict                # span name -> seconds
    self_seconds: dict           # span name -> seconds less direct children
    count: dict                  # span name -> spans in the window
    arg_sums: dict               # span name -> {numeric argument: sum}
    what_seconds: dict           # (span name, what) -> seconds
    idle_by_span: dict           # innermost span name or NONE -> idle s,
    #                              summed over devices

    @property
    def idle_s(self) -> float:
        return sum(self.idle_by_span.values())

    @property
    def unattributed_s(self) -> float:
        """Idle seconds under no span, or under a container span only."""
        return sum(s for name, s in self.idle_by_span.items()
                   if name == NONE or name in CONTAINERS)

    def total(self, *names: str) -> float:
        return sum(self.seconds.get(n, 0.0) for n in names)

    def arg_sum(self, arg: str, *names: str) -> float:
        return sum(self.arg_sums.get(n, {}).get(arg, 0) for n in names)


class _Innermost:
    """The shortest span over each instant: a sweep over span boundaries.
    ``at[k]`` starts the k-th stretch of time, over which ``name[k]`` is
    innermost; the last stretch, after every span, is under none."""

    def __init__(self, spans):
        events = sorted({t for s, e, _ in spans for t in (s, e)})
        starts = sorted(spans)
        heap, i = [], 0
        self.at, self.name = [], []
        for t in events:
            while i < len(starts) and starts[i][0] <= t:
                s, e, name = starts[i]
                heapq.heappush(heap, (e - s, e, name))
                i += 1
            while heap and heap[0][1] <= t:
                heapq.heappop(heap)
            name = heap[0][2] if heap else NONE
            if not self.name or name != self.name[-1]:
                self.at.append(t)
                self.name.append(name)

    def split(self, lo, hi):
        """[(name, length)] of the stretches that cover [lo, hi)."""
        out = []
        k = bisect.bisect_right(self.at, lo) - 1
        t = lo
        while t < hi:
            end = self.at[k + 1] if k + 1 < len(self.at) else hi
            end = min(end, hi)
            out.append((self.name[k] if k >= 0 else NONE, end - t))
            t, k = end, k + 1
        return out


def read(path: str) -> ProgramSpans | None:
    """Reduce the ``repro.`` spans of the trace at ``path`` over its
    ``bench.window`` annotation; None if the window holds none."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window = None
    lines = []                  # per host line: [(start, end, name, stats)]
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = []
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(PREFIX):
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name[len(PREFIX):], list(ev.stats)))
            if evs:
                lines.append(evs)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    lo, hi = window
    seconds = collections.defaultdict(float)
    self_s = collections.defaultdict(float)
    count = collections.Counter()
    args = collections.defaultdict(lambda: collections.defaultdict(float))
    what_s = collections.defaultdict(float)
    spans = []
    for evs in lines:
        evs = [(s, e, name, st) for s, e, name, st in
               ((*_clip(s, e, lo, hi), name, st) for s, e, name, st in evs)
               if e > s]
        # on one thread spans nest: a span's parent is the innermost open
        # span that holds it
        evs.sort(key=lambda ev: (ev[0], -ev[1]))
        stack = []
        for s, e, name, stats in evs:
            d = (e - s) * 1e-9
            seconds[name] += d
            self_s[name] += d
            count[name] += 1
            what = None
            for k, v in stats:
                if k == "what":
                    what = v
                elif isinstance(v, (int, float)):
                    args[name][k] += v
            if what is not None:
                what_s[(name, what)] += d
            while stack and stack[-1][0] <= s:
                stack.pop()
            if stack and e <= stack[-1][0]:
                self_s[stack[-1][1]] -= d
            stack.append((e, name))
            spans.append((s, e, name))
    if not spans:
        return None
    innermost = _Innermost(spans)
    idle = collections.defaultdict(float)
    busy = []
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                             lo, hi)
                if e > s:
                    ops.append((s, e))
        merged = _union(ops)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        t = lo
        for s, e in merged + [[hi, hi]]:
            if s > t:
                for name, d in innermost.split(t, s):
                    idle[name] += d * 1e-9
            t = max(t, e)
    return ProgramSpans(
        n_devices=len(devices), window_s=(hi - lo) * 1e-9, busy_s=busy,
        seconds=dict(seconds), self_seconds=dict(self_s), count=dict(count),
        arg_sums={k: dict(v) for k, v in args.items()},
        what_seconds=dict(what_s), idle_by_span=dict(idle))


_CACHE: dict = {}


def load(path: str) -> ProgramSpans | None:
    """``read(path)``, parsed once per file for every reader."""
    key = (os.path.abspath(path), os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE[key] = read(path)
    return _CACHE[key]


def of_run() -> ProgramSpans | None:
    """The program spans of a traced run of ``bench/run_cell.py``: the
    trace it reduced, under its fixed trace directory."""
    from bench.run_cell import TRACE_DIR

    return load(find_xplane(TRACE_DIR))
