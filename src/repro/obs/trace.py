"""Nested span tracing for the LU pipeline (DESIGN.md §12).

Zero-overhead-when-disabled is the design contract: every instrumentation
site in the pipeline calls ``span("name")``, and when spans are off that
call is a module-level boolean check returning a cached no-op context
manager — no ``Span`` allocation, no ``perf_counter`` read, no lock.  The
tier-1 bitwise gates and the committed bench ratio gates therefore see the
instrumented code paths unchanged.

Spans are live in two cases, checked once per public call (``ensure``):

* tracing is on (``tracing(path=...)``, ``enable()``, or
  ``LUOptions(trace=True)``): the active ``Tracer`` records one *complete*
  event per span — name, start, duration, track, nesting depth — with a
  per-thread span stack (``threading.local``) so the chunk driver's worker
  threads and the per-device segment sweeps each get coherent nesting, and
  a single lock protecting only the append to the shared event list;
* a JAX profiler session was collecting when the public call began: every
  span also opens a ``jax.profiler.TraceAnnotation`` named
  ``repro.<name>``, with the span's keyword arguments as its metadata, so
  the program's phases lie on the device planes' clock.  With the profiler
  alone, spans write to the profiler and nothing else (no ``Tracer``, no
  registry, ``.stats`` stays None).

Transfers between host and device go through ``fetch`` and ``put``: each
is a span carrying ``bytes`` and ``what``, so time spent waiting on the
device is named where the host blocks, not booked to whichever span
happens to sync first.

Exports:

* Chrome trace-event JSON (``Tracer.export_chrome`` / ``write_chrome``):
  ``ph="X"`` complete events with microsecond ``ts``/``dur``, one ``pid``
  per track (``track="device 3"`` spans land on their own Perfetto track,
  named via ``"M"`` metadata events).
* A picklable summary tree (``Tracer.summary`` -> ``SpanSummary``):
  spans aggregated by (depth, name) path with call counts and total
  seconds, rendered as an indented text tree — this is what
  ``LUPlan.stats`` / ``LUFactorization.stats`` carry.
* Flat phase totals (``Tracer.phase_totals``) for the bench ``metrics``
  blocks.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.obs.metrics import registry

ENABLED = False                 # a Tracer is on: spans, counters, .stats
SPANS = False                   # the span hot-path gate: ENABLED or _PROFILE
_PROFILE = False                # the profiler was collecting at the call
_TRACER: Optional["Tracer"] = None
_LOCK = threading.Lock()

_MAIN_TRACK = "main"


@dataclasses.dataclass
class SpanEvent:
    """One closed span, times in seconds relative to the tracer epoch."""

    name: str
    start: float
    dur: float
    track: str
    depth: int
    tid: int


class _NullSpan:
    """Cached do-nothing context manager returned while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """A live span: opens its profiler annotation (profiler collecting) and
    records its event on exit (tracer on)."""

    __slots__ = ("tracer", "name", "track", "args", "ann", "start", "depth")

    def __init__(self, tracer: Optional["Tracer"], name: str,
                 track: Optional[str], args: Optional[dict] = None):
        self.tracer = tracer
        self.name = name
        self.track = track
        self.args = args

    def __enter__(self):
        self.ann = None
        if _PROFILE:
            self.ann = TraceAnnotation("repro." + self.name,
                                       **(self.args or {}))
            self.ann.__enter__()
        if self.tracer is not None:
            tl = self.tracer._tl()
            if self.track is None:
                self.track = tl.track
            self.depth = len(tl.stack)
            tl.stack.append(self.name)
            self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.tracer is not None:
            end = time.perf_counter()
            tl = self.tracer._tl()
            tl.stack.pop()
            self.tracer._record(SpanEvent(
                name=self.name, start=self.start - self.tracer.epoch,
                dur=end - self.start, track=self.track, depth=self.depth,
                tid=threading.get_ident()))
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class Tracer:
    """Collects spans; thread-safe; one instance active at a time."""

    def __init__(self):
        self.epoch = time.perf_counter()
        self.events: List[SpanEvent] = []
        self._lock = threading.Lock()
        self._local = threading.local()

    def _tl(self):
        tl = self._local
        if not hasattr(tl, "stack"):
            tl.stack = []
            tl.track = _MAIN_TRACK
        return tl

    def _record(self, ev: SpanEvent) -> None:
        with self._lock:
            self.events.append(ev)

    @contextlib.contextmanager
    def track(self, name: str):
        """Route this thread's spans to a named track (e.g. "device 2")."""
        tl = self._tl()
        prev = tl.track
        tl.track = name
        try:
            yield
        finally:
            tl.track = prev

    # ---- exports ---------------------------------------------------------

    def export_chrome(self) -> dict:
        """Chrome trace-event JSON object (Perfetto-loadable)."""
        with self._lock:
            events = list(self.events)
        tracks = sorted({ev.track for ev in events},
                        key=lambda t: (t != _MAIN_TRACK, t))
        pid_of = {t: i for i, t in enumerate(tracks)}
        out = []
        for t, pid in pid_of.items():
            out.append({"ph": "M", "name": "process_name", "pid": pid,
                        "tid": 0, "args": {"name": t}})
        for ev in events:
            out.append({
                "ph": "X",
                "name": ev.name,
                "ts": round(ev.start * 1e6, 3),
                "dur": round(ev.dur * 1e6, 3),
                "pid": pid_of[ev.track],
                "tid": ev.tid % 100000,
                "args": {"depth": ev.depth},
            })
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def write_chrome(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.export_chrome(), f)

    def mark(self) -> int:
        """Current event count — pass to ``summary``/``phase_totals`` to
        aggregate only spans recorded after this point."""
        with self._lock:
            return len(self.events)

    def summary(self, start: int = 0) -> "SpanSummary":
        """Aggregate events[start:] into a picklable ``SpanSummary`` tree.

        Spans nest by (track, tid, time containment); aggregation is by
        name path, so e.g. all ``factor_level`` spans under ``factorize``
        fold into one node with a call count.
        """
        with self._lock:
            events = list(self.events[start:])
        root = SpanSummary(name="total", count=1, total_s=0.0, children=[])
        # Rebuild ancestry per (track, tid) from start/end ordering: a span
        # is a child of the innermost open span that contains it.
        by_thread: Dict[Tuple[str, int], List[SpanEvent]] = {}
        for ev in events:
            by_thread.setdefault((ev.track, ev.tid), []).append(ev)
        for evs in by_thread.values():
            # sort by start; containment via an explicit stack of (end, node)
            evs.sort(key=lambda e: (e.start, -e.dur))
            stack: List[Tuple[float, SpanSummary]] = []
            for ev in evs:
                while stack and ev.start >= stack[-1][0] - 1e-12:
                    stack.pop()
                parent = stack[-1][1] if stack else root
                node = parent.child(ev.name)
                node.count += 1
                node.total_s += ev.dur
                stack.append((ev.start + ev.dur, node))
        root.total_s = sum(c.total_s for c in root.children)
        return root

    def phase_totals(self, start: int = 0) -> Dict[str, dict]:
        """Flat {name: {count, total_s}} roll-up (all depths merged)."""
        with self._lock:
            events = list(self.events[start:])
        out: Dict[str, dict] = {}
        for ev in events:
            d = out.setdefault(ev.name, {"count": 0, "total_s": 0.0})
            d["count"] += 1
            d["total_s"] += ev.dur
        for d in out.values():
            d["total_s"] = float(d["total_s"])
        return out


@dataclasses.dataclass
class SpanSummary:
    """Aggregated span tree node — picklable, carried on plan/factor
    ``.stats`` so a traced analysis can be saved and inspected later."""

    name: str
    count: int
    total_s: float
    children: List["SpanSummary"] = dataclasses.field(default_factory=list)

    def child(self, name: str) -> "SpanSummary":
        for c in self.children:
            if c.name == name:
                return c
        c = SpanSummary(name=name, count=0, total_s=0.0, children=[])
        self.children.append(c)
        return c

    def find(self, name: str) -> Optional["SpanSummary"]:
        """Depth-first lookup by span name."""
        for c in self.children:
            if c.name == name:
                return c
            hit = c.find(name)
            if hit is not None:
                return hit
        return None

    def render(self, indent: int = 0) -> str:
        """Indented text tree: name, total seconds, call count."""
        lines = []
        pad = "  " * indent
        lines.append(f"{pad}{self.name:<28s} {self.total_s * 1e3:10.2f} ms"
                     f"  x{self.count}")
        for c in sorted(self.children, key=lambda c: -c.total_s):
            lines.append(c.render(indent + 1))
        return "\n".join(lines)

    def __str__(self) -> str:
        return self.render()


# ---- module-level API (what the pipeline calls) --------------------------

def span(name: str, *, track: Optional[str] = None, **args):
    """Open a nested span.  THE hot-path entry point: when spans are off
    this is one global-bool check plus returning a cached null object.
    ``args`` become the profiler annotation's metadata (counts, bytes)."""
    if not SPANS:
        return _NULL_SPAN
    return _Span(_TRACER, name, track, args)


def traced(name: Optional[str] = None) -> Callable:
    """Decorator form of ``span`` (span name defaults to the function's)."""
    def deco(fn):
        sname = name or fn.__name__

        def wrapper(*args, **kwargs):
            if not SPANS:
                return fn(*args, **kwargs)
            with _Span(_TRACER, sname, None):
                return fn(*args, **kwargs)
        wrapper.__name__ = fn.__name__
        wrapper.__doc__ = fn.__doc__
        wrapper.__wrapped__ = fn
        return wrapper
    return deco


def device_track(device: Optional[int]):
    """Context routing this thread's spans to a per-device track; a no-op
    null context when tracing is off or ``device`` is None."""
    if not ENABLED or device is None:
        return _NULL_SPAN
    return _TRACER.track(f"device {int(device)}")


def tracer() -> Optional[Tracer]:
    """The active tracer, or None when disabled."""
    return _TRACER


def _set(enabled: bool, tracer: Optional[Tracer], profile: bool) -> None:
    """Install the gate state; the caller holds ``_LOCK``."""
    global ENABLED, SPANS, _PROFILE, _TRACER
    ENABLED, _TRACER, _PROFILE = enabled, tracer, profile
    SPANS = enabled or profile


def enable() -> Tracer:
    """Switch tracing on (idempotent); returns the active tracer."""
    with _LOCK:
        _set(True, _TRACER if _TRACER is not None else Tracer(), _PROFILE)
        return _TRACER


def disable() -> Optional[Tracer]:
    """Switch tracing off; returns the tracer that was active (so callers
    can still export), clearing the global slot."""
    with _LOCK:
        tr = _TRACER
        _set(False, None, _PROFILE)
        return tr


@contextlib.contextmanager
def tracing(path=None):
    """``with repro.obs.tracing("trace.json"):`` — enable for the block,
    write Chrome trace JSON to ``path`` on exit, restore the prior state."""
    prev_enabled, prev_tracer = ENABLED, _TRACER
    tr = enable()
    try:
        yield tr
    finally:
        with _LOCK:
            _set(prev_enabled, prev_tracer, _PROFILE)
        if path is not None:
            tr.write_chrome(path)


@contextlib.contextmanager
def ensure(flag: bool = False):
    """The gate every public call opens (``analyze``, ``replan``,
    ``factorize``, ``factorize_batch``, ``solve``, ``solve_batch``).

    Asks once whether a JAX profiler session is collecting; if so, spans
    inside the block annotate the profiler's trace.  Enables tracing for
    the block iff ``flag`` (``LUOptions(trace=True)``) and it is not
    already on.  Yields the active tracer (or None).  Never disables a
    tracer someone outside the block owns."""
    outer = _PROFILE
    profile = TraceAnnotation.is_enabled()
    with _LOCK:
        installed = None
        if flag and not ENABLED:
            installed = Tracer()
            _set(True, installed, profile)
        else:
            _set(ENABLED, _TRACER, profile)
    try:
        yield _TRACER if ENABLED else None
    finally:
        with _LOCK:
            # only tear down if still the tracer we installed
            if installed is not None and _TRACER is installed:
                _set(False, None, outer)
            else:
                _set(ENABLED, _TRACER, outer)


# ---- host <-> device transfers -------------------------------------------

def _nbytes(x) -> int:
    return sum(int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(x))


def fetch(x, what: str):
    """``x`` (a device array, or a tuple of them) on the host as numpy.

    A tuple's copies all start before the first one is waited on.  While
    spans are live the transfer runs inside a ``fetch`` span with
    ``bytes`` and ``what``: it times waiting on the device plus the copy.
    With tracing on it also counts ``transfer.bytes_to_host``.  A numpy
    array is already on the host: it comes back as it is, with no span."""
    if not SPANS:
        return jax.device_get(x) if isinstance(x, tuple) else np.asarray(x)
    if isinstance(x, np.ndarray):
        return x
    nbytes = _nbytes(x)
    with _Span(_TRACER, "fetch", None, {"bytes": nbytes, "what": what}):
        out = jax.device_get(x) if isinstance(x, tuple) else np.asarray(x)
    if ENABLED:
        registry().count("transfer.bytes_to_host", nbytes)
    return out


def put(x, what: str) -> jax.Array:
    """Host array ``x`` on the default device (``jnp.asarray``).  While
    spans are live the transfer runs inside a ``put`` span with ``bytes``
    and ``what``; with tracing on it also counts
    ``transfer.bytes_to_device``.  A device array comes back as it is,
    with no span."""
    if not SPANS:
        return jnp.asarray(x)
    if isinstance(x, jax.Array):
        return x
    nbytes = int(x.nbytes)
    with _Span(_TRACER, "put", None, {"bytes": nbytes, "what": what}):
        out = jnp.asarray(x)
    if ENABLED:
        registry().count("transfer.bytes_to_device", nbytes)
    return out
