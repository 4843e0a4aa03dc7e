"""Multi-source concurrent symbolic factorization (paper §V).

* **Combined traversal** — a chunk of #C sources runs as ONE batched fixpoint:
  every vector lane works on whatever (source, vertex) tile is active,
  irrespective of the source — the dense-batch equivalent of the paper's shared
  frontier queue + tracker[] (the tracker is the batch index, free).
  ``combined=False`` runs the same chunk one source at a time (the paper's
  "#C = 1" baseline in Fig 12).

* **Chunk planning with bubble removal** — sources are processed in ascending
  chunks; since a source ``src`` never *expands* vertices >= src, the label
  matrix of a chunk only needs width ``max(src in chunk) + 1`` (rounded for
  retrace stability).  U-part fills beyond the window are pure reachability
  (any discovered path has intermediates < src < v, so Theorem 1 collapses —
  paper §VI "bubble removal", which keeps fill[] full-width but shrinks
  maxId[]); we recover them with one full-width relaxation pass at convergence.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gsofa
from repro.core.gsofa import (
    INF, SymbolicGraph, compute_prop, fill_masks, init_labels, relax_ell,
    row_counts,
)
from repro.core.spaceopt import LabelArena, auto_concurrency
from repro.obs import metrics as _om
from repro.obs import trace as _ot


@dataclasses.dataclass(frozen=True)
class Chunk:
    srcs: np.ndarray     # (S,) int32, padded to full concurrency with repeats
    n_real: int          # how many leading entries are real sources
    width: int           # label width (bubble removal), <= graph.n


def plan_chunks(n: int, concurrency: int, *, bubble: bool = False,
                round_to: int = 256) -> List[Chunk]:
    """Ascending source chunks.  Padding repeats the last source (idempotent —
    duplicate sources converge to identical labels; the extras are sliced off)."""
    chunks: List[Chunk] = []
    for start in range(0, n, concurrency):
        srcs = np.arange(start, min(start + concurrency, n), dtype=np.int32)
        n_real = len(srcs)
        if n_real < concurrency:
            srcs = np.concatenate(
                [srcs, np.full(concurrency - n_real, srcs[-1], dtype=np.int32)])
        if bubble:
            width = min(n, math.ceil((int(srcs[:n_real].max()) + 1)
                                     / round_to) * round_to)
        else:
            width = n
        chunks.append(Chunk(srcs=srcs, n_real=n_real, width=width))
    return chunks


def _chunk_view(graph: SymbolicGraph, width: int) -> SymbolicGraph:
    """Truncated view for bubble-removal chunks: only vertices < width can be
    relaxed/expanded; in-neighbor ids >= width are clipped to the INF pad slot."""
    if width >= graph.n:
        return graph
    return SymbolicGraph(
        n=width,
        in_ell=jnp.minimum(graph.in_ell[:width], jnp.int32(width)),
        out_ell=graph.out_ell,  # unused by the fixpoint (init passes nbrs)
        out_deg=graph.out_deg[:width],
        adj_dense=None,
    )


def _finalize_bubble(graph: SymbolicGraph, labels_w: jax.Array, srcs: jax.Array,
                     offset, width: int) -> jax.Array:
    """Full-width fill mask from a truncated-label fixpoint.

    v < width: Theorem-1 test on the converged labels.  v >= width (> src):
    reachability — one extra full-width relaxation of the converged props,
    plus the direct edges of each source.
    """
    n = graph.n
    prop = compute_prop(labels_w, srcs, width, offset)
    prop_full = jnp.pad(prop, ((0, 0), (0, n - width)), constant_values=INF)
    cand_full = relax_ell(prop_full, graph)                 # (S, n)
    v_ids = jnp.arange(n, dtype=jnp.int32)
    low = fill_masks(labels_w, srcs, offset)                # (S, width)
    direct = init_labels(graph, srcs) < INF                 # (S, n) original edges
    high = (cand_full < INF) | direct
    mask = jnp.concatenate(
        [low, high[:, width:]], axis=1) if width < n else low
    return mask & (v_ids[None, :] != srcs[:, None])


@dataclasses.dataclass
class MultiSourceResult:
    l_counts: np.ndarray        # (n,) structural L counts per row (no diag)
    u_counts: np.ndarray        # (n,)
    edge_checks: np.ndarray     # (n,) paper workload metric per source
    conv_iters: np.ndarray      # (n,) supersteps each source stayed active
    supersteps: int             # total supersteps across chunks
    n_chunks: int
    concurrency: int
    reinits: int                # real label re-initializations (window trick)
    windows: int

    @property
    def total_nnz(self) -> int:
        return int(self.l_counts.sum() + self.u_counts.sum() + len(self.l_counts))


def run_multisource(graph: SymbolicGraph, *, concurrency: int = 64,
                    backend: str = "ell", combined: bool = True,
                    bubble: bool = False, use_arena: bool = True,
                    budget_bytes: Optional[int] = None,
                    sources: Optional[np.ndarray] = None,
                    collect_masks: bool = False,
                    on_chunk: Optional[Callable] = None,
                    on_mask: Optional[Callable] = None,
                    on_progress: Optional[Callable] = None
                    ) -> MultiSourceResult:
    """Single-device multi-source driver: plan chunks, run fixpoints, aggregate.

    ``on_chunk(labels, srcs, offset)`` is invoked with every converged label
    matrix before it is recycled — labels is the (G, W) device array (W < n
    for bubble chunks), srcs the matching source ids (repeats possible from
    padding), offset the label-window base.  This is how supernode
    fingerprinting (repro.supernodes) overlaps detection with the symbolic
    chunks instead of gathering the dense pattern afterwards.

    ``on_mask(mask, srcs)`` receives the *full-width* (G, n) bool fill mask
    of each converged chunk (bubble chunks are finalized to full width
    first) — this is how the sparse CSC pattern streams out of the fixpoint
    (core.symbolic.PatternCollector) without ever gathering a dense (n, n)
    pattern on the host: each delivery is O(concurrency * n) and is reduced
    to per-row index lists before the next chunk arrives.

    ``on_progress(done, total, eta_s)`` fires once per completed chunk with
    a rolling-rate ETA (``repro.obs.ProgressMeter``) — the opt-in progress
    surface for long analyzes (bbd-20k runs ~88 s otherwise silent).
    """
    n = graph.n
    concurrency = auto_concurrency(graph, budget_bytes, concurrency, backend)
    if not combined:
        concurrency = max(1, concurrency)
    chunks = plan_chunks(n, concurrency, bubble=bubble)
    if sources is not None:
        # explicit source set (distributed callers slice their shard)
        chunks = []
        for start in range(0, len(sources), concurrency):
            srcs = np.asarray(sources[start:start + concurrency], dtype=np.int32)
            n_real = len(srcs)
            if n_real < concurrency:
                srcs = np.concatenate(
                    [srcs, np.full(concurrency - n_real, srcs[-1], np.int32)])
            chunks.append(Chunk(srcs=srcs, n_real=n_real, width=n))

    arena = None
    if use_arena and not bubble:
        arena = LabelArena(capacity=concurrency, n=n)

    l_counts = np.zeros(n, dtype=np.int64)
    u_counts = np.zeros(n, dtype=np.int64)
    edge_checks = np.zeros(n, dtype=np.int64)
    conv_iters = np.zeros(n, dtype=np.int64)
    masks = np.zeros((n, n), dtype=bool) if collect_masks else None
    supersteps = 0

    meter = _om.ProgressMeter(on_progress) if on_progress is not None else None
    for ci, chunk in enumerate(chunks):
        srcs = _ot.put(chunk.srcs, "chunk sources")
        if combined:
            groups = [np.arange(len(chunk.srcs))]
        else:
            groups = [np.array([i]) for i in range(chunk.n_real)]
        for g in groups:
            with _ot.span("fixpoint_chunk"):
                # the chunk's device programs, dispatched one by one (label
                # set-up, the fixpoint, fill masks, row counts)
                with _ot.span("chunk_dispatch"):
                    gs = srcs[jnp.asarray(g)]
                    if bubble and chunk.width < n:
                        offset = 0
                        view = _chunk_view(graph, chunk.width)
                        nbrs = graph.out_ell[gs]
                        labels0 = init_labels(view, gs, nbrs=nbrs)
                        res = gsofa.gsofa_batch(view, gs, backend="ell",
                                                labels0=labels0,
                                                max_iters=chunk.width + 2)
                        mask = _finalize_bubble(graph, res.labels, gs, 0,
                                                chunk.width)
                        v_ids = jnp.arange(n, dtype=jnp.int32)
                        l_cnt = jnp.sum(
                            mask & (v_ids[None, :] < gs[:, None]), axis=1)
                        u_cnt = jnp.sum(
                            mask & (v_ids[None, :] > gs[:, None]), axis=1)
                    else:
                        offset = 0
                        labels0 = None
                        if arena is not None and combined:
                            offset = arena.next_window()
                            labels0 = init_labels(graph, gs, offset=offset,
                                                  stale_buf=arena.buf)
                        res = gsofa.gsofa_batch(graph, gs, backend=backend,
                                                labels0=labels0,
                                                offset=offset)
                        if arena is not None and combined:
                            arena.buf = res.labels
                        mask = None
                        if collect_masks or on_mask is not None:
                            mask = fill_masks(res.labels, gs, offset)
                        l_cnt, u_cnt = row_counts(res.labels, gs, offset)

                if on_chunk is not None:
                    on_chunk(res.labels, chunk.srcs[np.asarray(g)], offset)
                if on_mask is not None:
                    on_mask(mask, chunk.srcs[np.asarray(g)])
                real = np.asarray(g) < chunk.n_real
                real_idx = chunk.srcs[np.asarray(g)[real]]
                l_h, u_h, edges_h, conv_h, iters = _ot.fetch(
                    (l_cnt, u_cnt, res.edge_checks, res.conv_iter,
                     res.iters), "chunk counts")
                l_counts[real_idx] = l_h[real]
                u_counts[real_idx] = u_h[real]
                edge_checks[real_idx] = edges_h[real]
                conv_iters[real_idx] = conv_h[real]
                supersteps += int(iters)
                if collect_masks and mask is not None:
                    masks[real_idx] = _ot.fetch(mask, "chunk mask")[real]
                if _ot.ENABLED:
                    _om.registry().observe("fixpoint.iterations", int(iters))
                    _om.registry().count("fixpoint.chunks")
        if meter is not None:
            meter.update(ci + 1, len(chunks))

    result = MultiSourceResult(
        l_counts=l_counts, u_counts=u_counts, edge_checks=edge_checks,
        conv_iters=conv_iters, supersteps=supersteps, n_chunks=len(chunks),
        concurrency=concurrency,
        reinits=arena.reinits if arena else len(chunks),
        windows=arena.windows if arena else len(chunks),
    )
    if collect_masks:
        result.masks = masks  # type: ignore[attr-defined]
    return result
