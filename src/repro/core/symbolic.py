"""Public API: the paper's technique as a first-class framework feature.

``symbolic_factorize`` is what a solver integration (e.g. the paper's planned
SuperLU_DIST integration) calls: CSR in, L/U structure out, with the paper's
knobs (concurrency, combined traversal, interleaving, memory envelope) and
framework-grade fault tolerance (chunk checkpointing, restart, work stealing
via runtime.scheduler).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Optional

import numpy as np

from repro.core.gsofa import SymbolicGraph, prepare_graph
from repro.core.multisource import MultiSourceResult, run_multisource
from repro.core.spaceopt import aux_memory_report, auto_concurrency
from repro.obs import metrics as _om
from repro.obs import trace as _ot
from repro.sparse.csr import CSRMatrix


@dataclasses.dataclass
class SymbolicResult:
    n: int
    l_counts: np.ndarray          # per-row strictly-lower structural counts
    u_counts: np.ndarray          # per-row strictly-upper structural counts
    fill_ratio: float             # #fill-ins / nnz(A)  (Table I statistic)
    concurrency: int              # effective #C after the memory envelope
    supersteps: int
    reinits: int
    elapsed_s: float
    memory_report: dict
    # supernode partition (detect_supernodes=True; repro.supernodes pipeline)
    supernodes: Optional[np.ndarray] = None   # (n_supernodes, 2) [start, end)
    n_supernodes: int = 0
    mean_supernode_size: float = 0.0
    # sparse L+U pattern streamed from the fixpoint (collect_pattern=True) —
    # a storage.CSCPattern; the large-n path's replacement for dense_pattern
    pattern: Optional[object] = None
    # merged per-column fingerprints (detect_supernodes=True) — a
    # supernodes.ColumnFingerprints, O(n) and picklable.  Retained so
    # autotune/replan can re-detect partitions under different relax /
    # max_size knobs without re-running the fixpoint (DESIGN.md §16).
    fingerprints: Optional[object] = None

    @property
    def lu_nnz(self) -> int:
        return int(self.l_counts.sum() + self.u_counts.sum() + self.n)


class ChunkCheckpointer:
    """Fault tolerance for long symbolic runs: per-chunk durable progress.

    The source space is embarrassingly parallel, so the natural checkpoint
    unit is a completed *source range*; restart resumes whatever sources are
    not covered by any record (a node failure loses at most one in-flight
    chunk).  Coverage is tracked per source, not per chunk-grid start, so a
    restart may use a different ``concurrency`` than the recording run —
    pending work is re-chunked on the new grid.
    """

    def __init__(self, path: str, n: int):
        self.path = path
        self.n = n
        self.records: list[dict] = []
        self.covered = np.zeros(n, dtype=bool)
        self.done: dict[int, dict] = {}    # start -> latest rec (introspection)
        if os.path.exists(path):
            with open(path) as f:
                for line in f:
                    rec = json.loads(line)
                    if rec["n"] == n:
                        self._remember(rec)

    def _remember(self, rec: dict) -> None:
        self.records.append(rec)
        self.covered[np.asarray(rec["srcs"], dtype=np.int64)] = True
        self.done[rec["start"]] = rec

    def pending_sources(self) -> np.ndarray:
        """Sources not covered by any record, ready to be re-chunked on
        whatever concurrency grid the restarting run uses."""
        return np.flatnonzero(~self.covered).astype(np.int64)

    def record(self, start: int, srcs: np.ndarray, l_cnt: np.ndarray,
               u_cnt: np.ndarray) -> None:
        rec = {"n": self.n, "start": int(start), "srcs": srcs.tolist(),
               "l": l_cnt.tolist(), "u": u_cnt.tolist()}
        with open(self.path, "a") as f:
            f.write(json.dumps(rec) + "\n")
            f.flush()
            os.fsync(f.fileno())
        self._remember(rec)

    def restore_into(self, l_counts: np.ndarray, u_counts: np.ndarray) -> int:
        for rec in self.records:
            srcs = np.asarray(rec["srcs"], dtype=np.int64)
            l_counts[srcs] = np.asarray(rec["l"], dtype=np.int64)
            u_counts[srcs] = np.asarray(rec["u"], dtype=np.int64)
        return int(self.covered.sum())


def detect_supernodes(pattern: np.ndarray, *, max_size: int = 64) -> np.ndarray:
    """Supernode partition of the filled pattern (paper §V: supported even
    under interleaved source assignment, since it is a post-pass over the
    gathered structure).

    Columns j-1, j share a supernode iff L(j:, j) and L(j:, j-1) have the
    same nonzero structure and L(j, j-1) != 0 (the SuperLU T2 test).
    Returns an (n_supernodes, 2) array of [start, end) column ranges —
    consumed by supernodal numeric factorization to batch dense updates.

    This is the dense *test oracle* for the streamed fingerprint detector
    (repro.supernodes); it is vectorized — one shifted-column structure
    comparison instead of a per-column ``np.array_equal`` loop — but stays
    bitwise-identical to the serial scan (tests hold it to that contract).
    """
    pattern = np.asarray(pattern, dtype=bool)
    n = pattern.shape[0]
    if n == 0:
        return np.zeros((0, 2), dtype=np.int64)
    # mergeable[j] (j >= 1): L(j:, j) == L(j:, j-1) structurally and
    # L(j, j-1) != 0.  The suffix comparison vectorizes as "the last row
    # where adjacent columns disagree sits strictly above row j".
    diff = pattern[:, 1:] != pattern[:, :-1]            # (n, n-1)
    rows = np.arange(n, dtype=np.int64)
    last_mismatch = np.where(diff, rows[:, None], -1).max(axis=0)   # (n-1,)
    flags = np.zeros(n, dtype=bool)
    flags[1:] = pattern[rows[1:], rows[1:] - 1] & (last_mismatch < rows[1:])
    # maximal merge runs, split every max_size columns — identical to the
    # serial scan's size-counter reset
    starts = np.flatnonzero(~flags)
    ends = np.append(starts[1:], n)
    reps = -(-(ends - starts) // max_size)
    piece = np.arange(int(reps.sum())) - np.repeat(np.cumsum(reps) - reps, reps)
    s = np.repeat(starts, reps) + piece * max_size
    e = np.minimum(s + max_size, np.repeat(ends, reps))
    return np.stack([s, e], axis=1)


class PatternCollector:
    """Streams the filled L+U structure out of the fixpoint as sparse rows.

    ``update`` consumes the (G, n) bool fill mask of each converged chunk
    exactly as ``run_multisource(on_mask=...)`` emits it (padded duplicate
    sources allowed; re-delivery is idempotent) and immediately reduces each
    row to its column-index list, so peak host memory is O(nnz(L+U)) + one
    chunk mask — never a dense (n, n) pattern.  ``to_csc`` transposes the
    row lists into the ``storage.CSCPattern`` the packed numeric path
    consumes; this is the large-n replacement for ``core.gsofa
    .dense_pattern`` (ROADMAP follow-up: CSC extraction straight from the
    fixpoint).
    """

    def __init__(self, n: int):
        self.n = n
        self.row_cols: list = [None] * n
        self.seen = np.zeros(n, dtype=bool)

    @property
    def complete(self) -> bool:
        return bool(self.seen.all())

    def update(self, mask, srcs: np.ndarray) -> int:
        """Accumulate one chunk's fill mask; returns #new rows consumed."""
        if not _ot.SPANS:
            return self._update(mask, srcs)
        with _ot.span("pattern_collect"):
            return self._update(mask, srcs)

    def _update(self, mask, srcs: np.ndarray) -> int:
        srcs = np.asarray(srcs, dtype=np.int64)
        _, first = np.unique(srcs, return_index=True)
        keep = first[~self.seen[srcs[first]]]
        if len(keep) == 0:
            return 0
        mask = _ot.fetch(mask, "chunk mask").astype(bool, copy=False)
        for i in keep:
            src = int(srcs[i])
            row = np.flatnonzero(mask[i]).astype(np.int64)
            d = np.searchsorted(row, src)
            if d >= len(row) or row[d] != src:      # diagonal always present
                row = np.insert(row, d, src)
            self.row_cols[src] = row
            self.seen[src] = True
        return len(keep)

    def to_csc(self):
        """CSR row lists -> ``storage.CSCPattern`` (sorted rows per column)."""
        from repro.numeric.storage import CSCPattern

        if not self.complete:
            missing = np.flatnonzero(~self.seen)
            raise ValueError(f"pattern incomplete: rows {missing[:8].tolist()}"
                             f"... of {self.n} were never collected")
        counts = np.array([len(r) for r in self.row_cols], dtype=np.int64)
        rows = np.repeat(np.arange(self.n, dtype=np.int64), counts)
        cols = (np.concatenate(self.row_cols) if self.n
                else np.zeros(0, dtype=np.int64))
        order = np.lexsort((rows, cols))
        indptr = np.zeros(self.n + 1, dtype=np.int64)
        np.add.at(indptr, cols + 1, 1)
        return CSCPattern(n=self.n, indptr=np.cumsum(indptr),
                          rowind=rows[order])


def _symbolic_factorize_distributed(a: CSRMatrix, graph: SymbolicGraph,
                                    mesh, *, concurrency: int, backend: str,
                                    budget_bytes: Optional[int],
                                    detect_supernodes: bool,
                                    supernode_relax: int,
                                    supernode_max_size: int,
                                    collect_pattern: bool,
                                    t0: float,
                                    on_progress=None) -> SymbolicResult:
    """Mesh-sharded symbolic pass (DESIGN.md §11): the multi-source fixpoint
    runs inside ``core.distributed``'s shard_map chunk step; per-shard
    supernode fingerprints accumulate from the streamed label matrices and
    merge through ``runtime.collectives.merge_fingerprint_shards``; the
    sparse CSC pattern streams through the same ``PatternCollector`` hook
    as the single-device path.  Per-source fixpoints are unique and
    chunking-independent, so every output (counts, supernodes, pattern) is
    bitwise-identical to the single-device result at any device count —
    the `tests/test_distributed_plan.py` conformance contract.
    """
    from repro.core.distributed import distributed_multisource
    from repro.core.spaceopt import aux_memory_report
    from repro.runtime.collectives import merge_fingerprint_shards

    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[ax] for ax in axes]))

    fp_shards = None
    on_shard_chunk = None
    if detect_supernodes:
        from repro.supernodes import ColumnFingerprints

        fp_shards = [ColumnFingerprints(n=a.n) for _ in range(n_shards)]

        def on_shard_chunk(d, labels, srcs):
            fp_shards[d].update(labels, srcs)

    collector = PatternCollector(n=a.n) if collect_pattern else None
    on_shard_mask = None
    if collector is not None:
        def on_shard_mask(d, mask, srcs):
            collector.update(mask, srcs)

    eff_c = auto_concurrency(graph, budget_bytes, concurrency, backend)
    with _ot.span("fixpoint"):
        ms = distributed_multisource(
            graph, mesh, concurrency=eff_c, backend=backend,
            on_shard_chunk=on_shard_chunk, on_shard_mask=on_shard_mask,
            on_progress=on_progress)

    sn_ranges = None
    sn_count = 0
    sn_mean = 0.0
    fp = None
    if fp_shards is not None:
        from repro.supernodes import detect_from_fingerprints, supernode_stats

        with _ot.span("fingerprint_merge"):
            if len(axes) == 1:
                # device-side merge: one ring collective per accumulator
                fp = merge_fingerprint_shards(mesh, axes[0], fp_shards)
            else:
                # multi-axis production meshes fold on the host (same result:
                # the merge is associative/commutative either way)
                fp = fp_shards[0]
                for shard in fp_shards[1:]:
                    fp.merge(shard)
        sn_ranges = detect_from_fingerprints(
            fp, relax=supernode_relax, max_size=supernode_max_size)
        stats = supernode_stats(sn_ranges)
        sn_count = stats["n_supernodes"]
        sn_mean = stats["mean_size"]

    row_ids = np.repeat(np.arange(a.n, dtype=np.int64), np.diff(a.indptr))
    nnz_offdiag = int(a.nnz) - int(np.count_nonzero(a.indices == row_ids))
    fills = int(ms.l_counts.sum() + ms.u_counts.sum()) - nnz_offdiag
    res = SymbolicResult(
        n=a.n, l_counts=ms.l_counts, u_counts=ms.u_counts,
        fill_ratio=fills / max(1, a.nnz),
        concurrency=ms.concurrency, supersteps=ms.supersteps,
        reinits=ms.reinits, elapsed_s=time.perf_counter() - t0,
        memory_report=aux_memory_report(graph, ms.concurrency, backend),
        supernodes=sn_ranges, n_supernodes=sn_count,
        mean_supernode_size=sn_mean,
        pattern=collector.to_csc() if collector is not None else None,
        fingerprints=fp,
    )
    res.dist = getattr(ms, "dist", None)       # type: ignore[attr-defined]
    _record_fill_metrics(res, a)
    return res


def _record_fill_metrics(res: SymbolicResult, a: CSRMatrix) -> None:
    """Device-count-invariant fill gauges (obs registry, DESIGN.md §12)."""
    if not _ot.ENABLED:
        return
    reg = _om.registry()
    reg.gauge("fill.lu_nnz", res.lu_nnz)
    reg.gauge("fill.input_nnz", int(a.nnz))


def symbolic_factorize(a: CSRMatrix, *, concurrency: int = 128,
                       backend: str = "ell", combined: bool = True,
                       bubble: bool = False, use_arena: bool = True,
                       budget_bytes: Optional[int] = None,
                       checkpoint_path: Optional[str] = None,
                       graph: Optional[SymbolicGraph] = None,
                       detect_supernodes: bool = False,
                       supernode_relax: int = 0,
                       supernode_max_size: int = 64,
                       collect_pattern: bool = False,
                       mesh=None, runtime: str = "static",
                       on_progress=None) -> SymbolicResult:
    """Compute the L/U nonzero structure of ``a``.

    With ``detect_supernodes=True`` the supernode partition rides along for
    free: per-chunk converged label matrices are folded into O(n) column
    fingerprints as they stream out of the fixpoint (repro.supernodes,
    DESIGN.md §3) — no dense pattern is ever gathered — and the result gains
    ``supernodes`` / ``n_supernodes`` / ``mean_supernode_size``.
    ``supernode_relax`` is the T3 merge tolerance (0 = exact T2);
    ``supernode_max_size`` caps panel width like the serial post-pass.

    With ``collect_pattern=True`` the sparse L+U structure streams out of
    the same fixpoint chunks (``PatternCollector``): the result gains
    ``pattern``, a ``storage.CSCPattern`` in O(nnz(L+U)) host memory —
    what ``repro.analyze`` feeds the packed numeric path at any n, with no
    dense (n, n) gather anywhere (DESIGN.md §10).

    With ``mesh`` (a ``jax.sharding.Mesh``; build one with
    ``launch.mesh.make_flat_mesh``) the fixpoint shards its sources over
    the mesh devices inside shard_map (DESIGN.md §11): fingerprints
    accumulate per shard and merge through device collectives, the
    pattern streams exactly as on one device, and every output is
    bitwise-identical to the mesh-less path.  The distributed path always
    runs combined chunks; ``bubble`` and ``checkpoint_path`` are
    single-device refinements and raise here, while ``use_arena`` is
    simply ignored (no label-arena windows inside shard_map).

    ``runtime="dynamic"`` routes the fixpoint through the work-stealing
    ``runtime.scheduler.DynamicScheduler`` instead of the static chunk
    loop (DESIGN.md §13): every visible device pulls chunks from a shared
    queue, stragglers are speculatively re-issued, and devices may
    join/leave mid-run — while the converged label matrices and fill
    masks stream into the *same* fingerprint/pattern collectors, so every
    output stays bitwise-identical to the static drivers.
    ``checkpoint_path`` composes with it (the scheduler skips covered
    chunks on restart); ``mesh`` and ``bubble`` do not (the scheduler
    *is* the distribution — one host driving the device pool).
    """
    t0 = time.perf_counter()
    if runtime not in ("static", "dynamic"):
        raise ValueError(f"unknown runtime {runtime!r}; pick from "
                         f"('static', 'dynamic')")
    if graph is None:
        dense_block = 128 if backend in ("dense", "kernel") else None
        with _ot.span("prepare_graph"):
            graph = prepare_graph(a, dense_block=dense_block)
    if mesh is not None:
        if runtime == "dynamic":
            raise ValueError(
                "runtime='dynamic' is the host-driven scheduler over the "
                "visible devices and cannot be combined with a shard_map "
                "mesh — drop one of the two")
        if checkpoint_path is not None:
            raise ValueError(
                "checkpoint_path is a single-device refinement; the "
                "distributed path re-runs lost shards instead (drop the "
                "mesh or the checkpoint)")
        if bubble:
            raise ValueError("bubble removal is not supported on the "
                             "distributed path (chunks are full-width)")
        return _symbolic_factorize_distributed(
            a, graph, mesh, concurrency=concurrency, backend=backend,
            budget_bytes=budget_bytes,
            detect_supernodes=detect_supernodes,
            supernode_relax=supernode_relax,
            supernode_max_size=supernode_max_size,
            collect_pattern=collect_pattern, t0=t0,
            on_progress=on_progress)
    eff_c = auto_concurrency(graph, budget_bytes, concurrency, backend)

    fp = None
    on_chunk = None
    if detect_supernodes:
        from repro.supernodes import ColumnFingerprints

        fp = ColumnFingerprints(n=a.n)
        on_chunk = fp.update
    collector = PatternCollector(n=a.n) if collect_pattern else None
    on_mask = collector.update if collector is not None else None

    ckpt = ChunkCheckpointer(checkpoint_path, a.n) if checkpoint_path else None
    runtime_stats = None
    if runtime == "dynamic":
        if bubble:
            raise ValueError("bubble removal is not supported on the "
                             "dynamic runtime (chunks are full-width)")
        from repro.runtime.scheduler import DynamicScheduler

        sched = DynamicScheduler(graph, concurrency=eff_c, backend=backend,
                                 checkpointer=ckpt, on_chunk=on_chunk,
                                 on_mask=on_mask)
        with _ot.span("fixpoint"):
            out = sched.run()
        ms = MultiSourceResult(
            l_counts=out["l_counts"], u_counts=out["u_counts"],
            edge_checks=out["edge_checks"],
            conv_iters=np.zeros(a.n, np.int64),
            supersteps=out["supersteps"], n_chunks=out["completed"],
            concurrency=eff_c, reinits=out["completed"],
            windows=out["completed"])
        runtime_stats = {
            "n_devices": len(sched.devices),
            "chunks": out["chunks"], "completed": out["completed"],
            "steals": out["steals"], "reissues": out["reissues"],
            "retired": out["retired"],
        }
    elif ckpt is not None and ckpt.covered.any():
        # restart path: only run the uncovered sources, re-chunked on THIS
        # run's grid (the recording run may have used a different concurrency)
        l_counts = np.zeros(a.n, dtype=np.int64)
        u_counts = np.zeros(a.n, dtype=np.int64)
        ckpt.restore_into(l_counts, u_counts)
        pending = ckpt.pending_sources()
        supersteps = reinits = n_chunks = 0
        with _ot.span("fixpoint"):
            for start in range(0, len(pending), eff_c):
                srcs = pending[start:start + eff_c].astype(np.int32)
                res = run_multisource(graph, concurrency=eff_c,
                                      backend=backend, combined=combined,
                                      bubble=bubble, use_arena=use_arena,
                                      sources=srcs, on_chunk=on_chunk,
                                      on_mask=on_mask)
                l_counts[srcs] = res.l_counts[srcs]
                u_counts[srcs] = res.u_counts[srcs]
                supersteps += res.supersteps
                reinits += res.reinits
                n_chunks += 1
                ckpt.record(int(srcs[0]), srcs, res.l_counts[srcs],
                            res.u_counts[srcs])
        ms = MultiSourceResult(
            l_counts=l_counts, u_counts=u_counts,
            edge_checks=np.zeros(a.n, np.int64), conv_iters=np.zeros(a.n, np.int64),
            supersteps=supersteps, n_chunks=n_chunks, concurrency=eff_c,
            reinits=reinits, windows=0)
    else:
        with _ot.span("fixpoint"):
            ms = run_multisource(graph, concurrency=eff_c, backend=backend,
                                 combined=combined, bubble=bubble,
                                 use_arena=use_arena,
                                 budget_bytes=budget_bytes,
                                 on_chunk=on_chunk, on_mask=on_mask,
                                 on_progress=on_progress)
        if ckpt is not None:
            for start in range(0, a.n, eff_c):
                srcs = np.arange(start, min(start + eff_c, a.n), dtype=np.int64)
                ckpt.record(start, srcs, ms.l_counts[srcs], ms.u_counts[srcs])

    # checkpoint restart restored some chunks' counts without their label
    # matrices; re-run those sources once for whichever collectors miss them
    # (update() is idempotent, so one shared re-run feeds both)
    missing = np.zeros(a.n, dtype=bool)
    if fp is not None and not fp.complete:
        missing |= ~fp.seen
    if collector is not None and not collector.complete:
        missing |= ~collector.seen
    if missing.any():
        run_multisource(graph, concurrency=eff_c, backend=backend,
                        combined=combined, bubble=bubble,
                        use_arena=use_arena,
                        sources=np.flatnonzero(missing).astype(np.int32),
                        on_chunk=on_chunk, on_mask=on_mask)

    sn_ranges = None
    sn_count = 0
    sn_mean = 0.0
    if fp is not None:
        from repro.supernodes import detect_from_fingerprints, supernode_stats

        sn_ranges = detect_from_fingerprints(
            fp, relax=supernode_relax, max_size=supernode_max_size)
        stats = supernode_stats(sn_ranges)
        sn_count = stats["n_supernodes"]
        sn_mean = stats["mean_size"]

    row_ids = np.repeat(np.arange(a.n, dtype=np.int64), np.diff(a.indptr))
    nnz_offdiag = int(a.nnz) - int(np.count_nonzero(a.indices == row_ids))
    lu_offdiag = int(ms.l_counts.sum() + ms.u_counts.sum())
    fills = lu_offdiag - nnz_offdiag
    out = SymbolicResult(
        n=a.n, l_counts=ms.l_counts, u_counts=ms.u_counts,
        fill_ratio=fills / max(1, a.nnz),
        concurrency=ms.concurrency, supersteps=ms.supersteps, reinits=ms.reinits,
        elapsed_s=time.perf_counter() - t0,
        memory_report=aux_memory_report(graph, ms.concurrency, backend),
        supernodes=sn_ranges, n_supernodes=sn_count,
        mean_supernode_size=sn_mean,
        pattern=collector.to_csc() if collector is not None else None,
        fingerprints=fp,
    )
    if runtime_stats is not None:
        out.runtime = runtime_stats            # type: ignore[attr-defined]
    _record_fill_metrics(out, a)
    return out
