"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

Device planes are ``/device:TPU:<i>``.  On each, the ``XLA Ops`` line holds
one event per operation run and the ``XLA Modules`` line one per program
(jit name, with the program id in brackets stripped).  Host annotations
(``jax.profiler.TraceAnnotation``) lie on the host plane on the same
clock; the benchmark opens ``bench.window`` around its measured window and
``bench.<call>`` around each call into the program.

* busy: the union of the operation intervals inside the window, per device;
* device seconds per operation and per program, summed over devices;
* collective seconds: operations whose name is a collective's;
* idle gaps: the window minus the busy union, per device, each gap put
  under the innermost ``bench.`` span that holds its midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|collective-permute|all-to-all|reduce-scatter"
    r"|ppermute|psum|send|recv", re.IGNORECASE)
PROGRAM_ID = re.compile(r"\(\d+\)$")
WINDOW = "bench.window"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Reduction:
    n_devices: int
    window_s: float
    busy_s: list                 # per device, inside the window
    op_s: dict                   # operation name -> device seconds
    module_s: dict               # program name -> device seconds
    collective_s: float          # device seconds in collectives
    idle_by_span: dict           # enclosing bench span -> idle device seconds
    spans: dict                  # bench span name -> count inside the window

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    def module_seconds(self, pattern: str) -> float:
        """Device seconds in programs whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for m, s in self.module_s.items() if rx.search(m))

    def top(self, table: dict, k: int = 10) -> list:
        return [[name, s] for name, s in
                sorted(table.items(), key=lambda kv: -kv[1])[:k]]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(path: str) -> Reduction:
    """Reduce the trace at ``path`` over its ``bench.window`` annotation."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window = None
    spans = []
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    lo, hi = window
    spans = [sp for sp in spans if sp[1] > lo and sp[0] < hi]
    span_count = collections.Counter(name for _, _, name in spans)
    spans.sort(key=lambda sp: sp[1] - sp[0])      # innermost first

    def label(t):
        for s, e, name in spans:
            if s <= t < e:
                return name
        return "outside bench spans"

    op_s = collections.defaultdict(float)
    module_s = collections.defaultdict(float)
    idle = collections.defaultdict(float)
    busy, collective = [], 0.0
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                             lo, hi)
                if e <= s:
                    continue
                if line.name == "XLA Modules":
                    module_s[PROGRAM_ID.sub("", ev.name)] += (e - s) * 1e-9
                    continue
                ops.append((s, e))
                op_s[ev.name] += (e - s) * 1e-9
                if COLLECTIVE.search(ev.name):
                    collective += (e - s) * 1e-9
        merged = _union(ops)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        t = lo
        for s, e in merged + [[hi, hi]]:
            if s > t:
                idle[label((t + s) / 2)] += (s - t) * 1e-9
            t = max(t, e)
    if not busy:
        raise ValueError(f"{path}: no TPU device plane with operations")
    return Reduction(n_devices=len(busy), window_s=(hi - lo) * 1e-9,
                     busy_s=busy, op_s=dict(op_s), module_s=dict(module_s),
                     collective_s=collective, idle_by_span=dict(idle),
                     spans=dict(span_count))
