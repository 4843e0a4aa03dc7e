"""Dynamic chunk scheduler: work stealing + straggler re-issue + elastic
scaling, plan-integrated (DESIGN.md §13).

The SPMD shard_map path (core.distributed) assigns sources statically; on a
real 1,000-GPU run, stragglers (slow/failed nodes) break static balance.  This
host-driven scheduler treats source chunks as a work queue over the *same*
chunk-step closure the static drivers run (``core.distributed.make_chunk_
step``): each completed chunk streams its converged label matrix and fill
mask back to the host, so supernode fingerprints and the sparse pattern
accumulate exactly as in ``run_multisource`` / ``distributed_multisource`` —
which is what lets ``repro.analyze`` itself run on this scheduler
(``LUOptions(runtime="dynamic")``, ``core.symbolic``).

* each device pulls the next chunk when its previous one completes (work
  stealing — the fast devices naturally absorb the straggler's queue; a pull
  of a chunk whose round-robin home is another device counts as a *steal*);
* a chunk whose device exceeds ``timeout_factor`` x the median chunk time is
  re-issued to an idle device (speculative re-execution; per-source fixpoints
  are unique and collector updates idempotent, so duplicates are harmless —
  and once any copy completes, the superseded flights are *retired* so their
  devices rejoin the idle pool instead of serving a dead race);
* devices can join/leave between chunks (elastic scaling) — the queue is
  indifferent to the device count;
* completed chunks go through the ChunkCheckpointer, so a full restart
  resumes pending work only.

Steal/re-issue/retire counts are reported both in the return dict and — when
tracing is enabled — as ``runtime.steals`` / ``runtime.reissues`` /
``runtime.retired`` counters in the obs registry; the whole drain loop runs
under a ``runtime`` span.

JAX dispatch is async: ``device_put`` + jitted call returns immediately and we
poll readiness via ``is_ready()`` on the output buffers.  Results are
delivered to the collectors exactly once per chunk (first copy wins), and
every per-source fixpoint is unique, so counts, fingerprints, and patterns
are bitwise-identical to the static drivers regardless of device count,
completion order, steals, or duplicated flights.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import make_chunk_step
from repro.core.gsofa import SymbolicGraph
from repro.core.symbolic import ChunkCheckpointer
from repro.obs import metrics as _om
from repro.obs import trace as _ot


@dataclasses.dataclass
class _InFlight:
    chunk_id: int
    srcs: np.ndarray             # unpadded sources of this chunk
    started: float
    outs: tuple                  # (labels, mask, l, u, edges, iters) futures


class DynamicScheduler:
    """Work-stealing scheduler over a set of JAX devices.

    ``on_chunk(labels, srcs, offset)`` receives each chunk's converged
    (G, n) label matrix exactly once (``ColumnFingerprints.update`` shape);
    ``on_mask(mask, srcs)`` the matching bool fill masks
    (``PatternCollector.update`` shape).  ``devices`` may repeat a physical
    device to model independent executor slots (tests use this to exercise
    steals and re-issues on a single-CPU host).
    """

    def __init__(self, graph: SymbolicGraph, *, devices: Optional[Sequence] = None,
                 concurrency: int = 64, backend: str = "ell",
                 timeout_factor: float = 4.0,
                 checkpointer: Optional[ChunkCheckpointer] = None,
                 on_chunk: Optional[Callable] = None,
                 on_mask: Optional[Callable] = None):
        self.graph = graph
        self.devices = list(devices if devices is not None else jax.devices())
        self.concurrency = concurrency
        self.backend = backend
        self.timeout_factor = timeout_factor
        self.ckpt = checkpointer
        self.on_chunk = on_chunk
        self.on_mask = on_mask
        self._step = make_chunk_step(graph.n, backend=backend)
        self._graphs: Dict[int, SymbolicGraph] = {}
        self._chunk_times: List[float] = []
        self.steals = 0
        self.reissues = 0
        self.retired = 0

    def _graph_on(self, dev) -> SymbolicGraph:
        key = id(dev)
        if key not in self._graphs:
            self._graphs[key] = jax.device_put(self.graph, dev)
        return self._graphs[key]

    def _launch(self, dev, chunk_id: int, srcs: np.ndarray) -> _InFlight:
        g = self._graph_on(dev)
        pad = self.concurrency - len(srcs)
        padded = (np.concatenate([srcs, np.full(pad, srcs[-1], np.int32)])
                  if pad else srcs)
        sj = jax.device_put(jnp.asarray(padded, jnp.int32), dev)
        outs = self._step(sj, g)
        return _InFlight(chunk_id=chunk_id, srcs=srcs,
                         started=time.perf_counter(), outs=outs)

    @staticmethod
    def _ready(flight: _InFlight) -> bool:
        return all(o.is_ready() for o in flight.outs)

    def run(self, *, drop_devices_after: Optional[int] = None,
            join_devices_after: Optional[int] = None) -> dict:
        """Process all chunks.

        ``drop_devices_after``: after N completed chunks, shrink to one
        device; ``join_devices_after``: start on one device and activate
        the rest after N completed chunks (elastic leave/join simulation
        for tests — the queue never cares how many devices are active).
        """
        if not _ot.ENABLED:
            return self._run(drop_devices_after, join_devices_after)
        with _ot.span("runtime"):
            return self._run(drop_devices_after, join_devices_after)

    def _run(self, drop_devices_after: Optional[int],
             join_devices_after: Optional[int]) -> dict:
        n = self.graph.n
        n_dev = len(self.devices)
        chunk_starts = list(range(0, n, self.concurrency))
        queue: collections.deque[int] = collections.deque()
        l_counts = np.zeros(n, dtype=np.int64)
        u_counts = np.zeros(n, dtype=np.int64)
        edge_checks = np.zeros(n, dtype=np.int64)
        for ci, start in enumerate(chunk_starts):
            srcs = np.arange(start, min(start + self.concurrency, n))
            # coverage is per source, not per grid start: a checkpoint
            # recorded under a different concurrency still restarts correctly
            # (a partially-covered chunk recomputes, which is idempotent)
            if self.ckpt is not None and self.ckpt.covered[srcs].all():
                continue
            queue.append(ci)
        if self.ckpt is not None:
            self.ckpt.restore_into(l_counts, u_counts)

        inflight: Dict[int, _InFlight] = {}   # device idx -> flight
        done_chunks: set[int] = set()
        completed = 0
        supersteps = 0
        active_devices = (list(range(n_dev)) if join_devices_after is None
                          else [0])

        def srcs_of(ci: int) -> np.ndarray:
            s = chunk_starts[ci]
            return np.arange(s, min(s + self.concurrency, n), dtype=np.int32)

        def consume(fl: _InFlight) -> None:
            """Deliver one chunk's results exactly once (first copy wins)."""
            nonlocal completed, supersteps
            labels, mask, l, u, edges, iters = (np.asarray(o)
                                                for o in fl.outs)
            k = len(fl.srcs)
            l_counts[fl.srcs] = l[:k]
            u_counts[fl.srcs] = u[:k]
            edge_checks[fl.srcs] = edges[:k]
            if self.on_chunk is not None:
                self.on_chunk(labels[:k], fl.srcs, 0)
            if self.on_mask is not None:
                self.on_mask(mask[:k], fl.srcs)
            supersteps += int(iters)
            done_chunks.add(fl.chunk_id)
            completed += 1
            self._chunk_times.append(time.perf_counter() - fl.started)
            if self.ckpt is not None:
                self.ckpt.record(chunk_starts[fl.chunk_id], fl.srcs,
                                 l[:k], u[:k])

        while queue or inflight:
            # fill idle devices; pulling a chunk whose round-robin home
            # device differs is a steal (static assignment would have put
            # chunk ci on device ci % n_dev)
            for d in list(active_devices):
                if d not in inflight and queue:
                    ci = queue.popleft()
                    if ci in done_chunks:
                        continue
                    if n_dev > 1 and ci % n_dev != d:
                        self.steals += 1
                    inflight[d] = self._launch(self.devices[d], ci, srcs_of(ci))
            if not inflight:
                break
            # poll
            progressed = False
            for d, fl in list(inflight.items()):
                if d not in inflight:          # retired this sweep
                    continue
                if self._ready(fl):
                    if fl.chunk_id not in done_chunks:
                        consume(fl)
                        # retire superseded duplicate flights: the race is
                        # decided, so losers must not keep occupying devices
                        for d2, fl2 in list(inflight.items()):
                            if d2 != d and fl2.chunk_id == fl.chunk_id:
                                del inflight[d2]
                                self.retired += 1
                        if (drop_devices_after is not None
                                and completed >= drop_devices_after
                                and len(active_devices) > 1):
                            active_devices = active_devices[:1]  # shrink
                        if (join_devices_after is not None
                                and completed >= join_devices_after
                                and len(active_devices) < n_dev):
                            active_devices = list(range(n_dev))   # join
                    del inflight[d]
                    progressed = True
                elif self._chunk_times:
                    # straggler: re-issue to an idle device (speculative)
                    med = float(np.median(self._chunk_times))
                    racing = any(f.chunk_id == fl.chunk_id
                                 for x, f in inflight.items() if x != d)
                    if (time.perf_counter() - fl.started > self.timeout_factor * med
                            and fl.chunk_id not in done_chunks and not racing):
                        idle = [x for x in active_devices if x not in inflight]
                        if idle:
                            self.reissues += 1
                            inflight[idle[0]] = self._launch(
                                self.devices[idle[0]], fl.chunk_id, fl.srcs)
            if not progressed:
                time.sleep(0.001)

        if _ot.ENABLED:
            reg = _om.registry()
            reg.count("runtime.steals", self.steals)
            reg.count("runtime.reissues", self.reissues)
            reg.count("runtime.retired", self.retired)
            reg.count("runtime.chunks", completed)

        return {"l_counts": l_counts, "u_counts": u_counts,
                "edge_checks": edge_checks,
                "chunks": len(chunk_starts), "completed": completed,
                "supersteps": supersteps,
                "steals": self.steals, "reissues": self.reissues,
                "retired": self.retired, "chunk_times": self._chunk_times}
