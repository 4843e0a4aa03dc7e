"""Distributed GSoFa: sources sharded across the device mesh via shard_map.

The paper scales to 1,000 GPUs because sources are *independent* once
per-source work is balanced; scaling is then purely a scheduling question:

* **interleaved (round-robin) source assignment** (paper §V, Fig 8): workload
  grows with the source id (Theorem 1 admits more intermediates), so a
  contiguous split loads late devices ~10x heavier; strided assignment
  ``src[d, i] = d + i * D`` flattens it to ~1.0x.
* each device runs the *combined traversal* over its local batch — exactly the
  single-device fixpoint; no collectives inside the loop (each device's
  while_loop trip count is its own), one all-gather of the per-source counts
  at the end (implicit in the shard_map output spec).

``make_distributed_counts`` returns the jitted shard_map step used both for
real execution (tests run it on 8 host devices) and for the 512-device
production-mesh dry-run (launch/dryrun.py lowers it with ShapeDtypeStructs).

``distributed_multisource`` is the *analyze* driver (DESIGN.md §11): the
same per-shard fixpoint, but streaming each converged chunk's label matrix
and fill mask back to the host so supernode fingerprints
(supernodes/fingerprint.py) accumulate per shard — merged afterwards
through ``runtime/collectives.merge_fingerprint_shards`` — and the sparse
``CSCPattern`` streams through the ``PatternCollector`` hook.  No dense
(n, n) pattern ever exists on any shard or on the host: each chunk step
moves O(n_shards * concurrency * n) labels, reduced to O(nnz) state before
the next step.  ``core.symbolic.symbolic_factorize(mesh=...)`` routes
through this driver, which is how ``repro.analyze`` distributes.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.gsofa import (
    SymbolicGraph, fill_masks, fixpoint_impl, init_labels, row_counts,
)
from repro.obs import metrics as _om
from repro.obs import trace as _ot


def assign_sources(n: int, n_shards: int, *, policy: str = "interleave") -> np.ndarray:
    """(n_shards, ceil(n / n_shards)) source matrix; short rows padded by
    repeating the row's last source (idempotent duplicates, sliced on return).

    interleave: src[d, i] = d + i * D   (paper's round-robin, Fig 8 'after')
    contiguous: src[d, i] = d * C + i   (the imbalanced baseline, Fig 8 'before')
    """
    per = -(-n // n_shards)
    total = per * n_shards
    ids = np.arange(total, dtype=np.int32)
    if policy == "interleave":
        mat = ids.reshape(per, n_shards).T
    elif policy == "contiguous":
        mat = ids.reshape(n_shards, per)
    else:
        raise ValueError(policy)
    mat = np.where(mat < n, mat, np.int32(n - 1))
    return np.ascontiguousarray(mat)


def _local_body(srcs_local: jax.Array, graph: SymbolicGraph, max_iters: int,
                backend: str) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-device work: batched fixpoint over the local source rows."""
    srcs = srcs_local.reshape(-1)
    labels0 = init_labels(graph, srcs)
    res = fixpoint_impl(graph, srcs, labels0, jnp.int32(0), backend, max_iters)
    l_cnt, u_cnt = row_counts(res.labels, srcs)
    shape = srcs_local.shape
    return (l_cnt.reshape(shape), u_cnt.reshape(shape),
            res.edge_checks.reshape(shape),
            jnp.broadcast_to(res.iters, (shape[0],)))


def make_distributed_counts(mesh: Mesh, graph_n: int, *, backend: str = "ell",
                            max_iters: Optional[int] = None,
                            axes: Optional[tuple] = None):
    """Build the jitted distributed step.

    The source matrix's leading axis is sharded over ``axes`` (default: every
    mesh axis, i.e. the fully-flattened device space — this is what scales the
    paper to 1,000 GPUs; for the LM production mesh it is ('pod','data','model')).
    The graph is replicated: symbolic factorization reads A everywhere but
    writes only its own rows, so the only communication is the final gather.
    """
    if axes is None:
        axes = tuple(mesh.axis_names)
    if max_iters is None:
        max_iters = graph_n + 2
    spec_src = P(axes, None)
    spec_rep = P()

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec_src, spec_rep),
        out_specs=(spec_src, spec_src, spec_src, P(axes)),
        # the while_loop carry mixes device-varying labels with replicated
        # scalars (trip counts differ per device by design) — disable the
        # varying-manual-axes check rather than pcast every carry leaf
        check_vma=False,
    )
    def body(srcs_mat, graph):
        return _local_body(srcs_mat, graph, max_iters, backend)

    in_shardings = (NamedSharding(mesh, spec_src), NamedSharding(mesh, spec_rep))
    out_shardings = (NamedSharding(mesh, spec_src), NamedSharding(mesh, spec_src),
                     NamedSharding(mesh, spec_src), NamedSharding(mesh, P(axes)))
    return jax.jit(body, in_shardings=in_shardings, out_shardings=out_shardings)


def distributed_symbolic(graph: SymbolicGraph, mesh: Mesh, *,
                         policy: str = "interleave", backend: str = "ell",
                         axes: Optional[tuple] = None) -> dict:
    """Run distributed symbolic factorization; returns counts + balance metrics."""
    if axes is None:
        axes = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    srcs = assign_sources(graph.n, n_shards, policy=policy)
    step = make_distributed_counts(mesh, graph.n, backend=backend, axes=axes)
    l_cnt, u_cnt, edges, iters = step(jnp.asarray(srcs), graph)
    l_cnt, u_cnt = np.asarray(l_cnt), np.asarray(u_cnt)
    edges = np.asarray(edges)
    # fold the (shard, slot) matrix back to per-source vectors, dropping pads
    l_out = np.zeros(graph.n, dtype=np.int64)
    u_out = np.zeros(graph.n, dtype=np.int64)
    seen = np.zeros(graph.n, dtype=bool)
    per_dev_edges = np.zeros(n_shards, dtype=np.int64)
    for d in range(n_shards):
        for i, s in enumerate(srcs[d]):
            if not seen[s]:
                l_out[s], u_out[s] = l_cnt[d, i], u_cnt[d, i]
                seen[s] = True
                per_dev_edges[d] += edges[d, i]
    balance = float(per_dev_edges.max()) / max(1.0, float(per_dev_edges.min()))
    return {
        "l_counts": l_out,
        "u_counts": u_out,
        "per_device_edge_checks": per_dev_edges,
        "balance_ratio": balance,
        "iters": np.asarray(iters),
        "n_shards": n_shards,
        "policy": policy,
    }


# ---------------------------------------------------------------------------
# distributed analyze: the fixpoint chunk step that streams labels + masks
# ---------------------------------------------------------------------------

def ownership_mask(srcs_mat: np.ndarray) -> np.ndarray:
    """(D, S) bool: True at the globally-first occurrence of each source.

    ``assign_sources`` pads short rows by clipping ids to ``n - 1``, so the
    last source can appear on several shards; exactly one shard must *own*
    each source or per-shard fingerprint partials would double-count on
    merge (``ColumnFingerprints.merge`` rejects overlapping shards for the
    same reason).
    """
    flat = srcs_mat.reshape(-1)
    owned = np.zeros(flat.shape, dtype=bool)
    _, first = np.unique(flat, return_index=True)
    owned[first] = True
    return owned.reshape(srcs_mat.shape)


def make_distributed_chunk_step(mesh: Mesh, graph_n: int, *,
                                backend: str = "ell",
                                max_iters: Optional[int] = None,
                                axes: Optional[tuple] = None):
    """Jitted shard_map step for ONE source chunk per device.

    In: (D, C) source matrix sharded over ``axes``; replicated graph.
    Out (all sharded on the leading axis): converged (D, C, n) label
    matrices, (D, C, n) bool fill masks, (D, C) l/u counts and edge
    checks, (D,) per-shard superstep counts.  The labels/masks leave the
    step so the host can feed the streaming supernode-fingerprint and
    pattern collectors — O(D * C * n) per step, never (n, n) anywhere.
    """
    if axes is None:
        axes = tuple(mesh.axis_names)
    if max_iters is None:
        max_iters = graph_n + 2
    spec_src = P(axes, None)
    spec_mat = P(axes, None, None)
    spec_rep = P()

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec_src, spec_rep),
        out_specs=(spec_mat, spec_mat, spec_src, spec_src, spec_src, P(axes)),
        check_vma=False,            # per-device while_loop trip counts differ
    )
    def body(srcs_mat, graph):
        srcs = srcs_mat.reshape(-1)                       # (C,) local chunk
        labels0 = init_labels(graph, srcs)
        res = fixpoint_impl(graph, srcs, labels0, jnp.int32(0), backend,
                            max_iters)
        mask = fill_masks(res.labels, srcs)
        l_cnt, u_cnt = row_counts(res.labels, srcs)
        lead = srcs_mat.shape                             # (1, C) local
        return (res.labels.reshape(lead + (graph.n,)),
                mask.reshape(lead + (graph.n,)),
                l_cnt.reshape(lead), u_cnt.reshape(lead),
                res.edge_checks.reshape(lead),
                jnp.broadcast_to(res.iters, (lead[0],)))

    shardings = {spec_src: NamedSharding(mesh, spec_src),
                 spec_mat: NamedSharding(mesh, spec_mat)}
    return jax.jit(
        body,
        in_shardings=(shardings[spec_src], NamedSharding(mesh, spec_rep)),
        out_shardings=(shardings[spec_mat], shardings[spec_mat],
                       shardings[spec_src], shardings[spec_src],
                       shardings[spec_src], NamedSharding(mesh, P(axes))))


def make_chunk_step(graph_n: int, *, backend: str = "ell",
                    max_iters: Optional[int] = None):
    """Jitted *single-device* chunk step — the mesh-less sibling of
    ``make_distributed_chunk_step``, and the closure the dynamic
    work-stealing scheduler (``runtime.scheduler``) launches per device.

    In: (C,) int32 sources + the (replicated) graph.  Out: converged
    (C, n) labels, (C, n) bool fill masks, (C,) l/u counts and edge
    checks, and the chunk's superstep count — exactly the streams the
    fingerprint/pattern collectors consume, so a host-driven scheduler
    can feed ``repro.analyze`` the same data the static drivers do.

    Dispatch is async: the returned callable hands back device arrays
    immediately; poll ``.is_ready()`` (or block via ``np.asarray``) on
    the outputs.  Per-source fixpoints are unique and chunking- and
    device-independent, so results are bitwise-identical no matter which
    device runs which chunk, in what order, or how many times.
    """
    if max_iters is None:
        max_iters = graph_n + 2

    @jax.jit
    def step(srcs, graph):
        labels0 = init_labels(graph, srcs)
        res = fixpoint_impl(graph, srcs, labels0, jnp.int32(0), backend,
                            max_iters)
        mask = fill_masks(res.labels, srcs)
        l_cnt, u_cnt = row_counts(res.labels, srcs)
        return res.labels, mask, l_cnt, u_cnt, res.edge_checks, res.iters

    return step


def distributed_multisource(graph: SymbolicGraph, mesh: Mesh, *,
                            concurrency: int = 128, backend: str = "ell",
                            policy: str = "interleave",
                            axes: Optional[tuple] = None,
                            on_shard_chunk: Optional[Callable] = None,
                            on_shard_mask: Optional[Callable] = None,
                            on_progress: Optional[Callable] = None):
    """Multi-source symbolic fixpoint sharded over the mesh, streaming each
    shard's converged chunks back to the host.

    ``on_shard_chunk(d, labels, srcs)`` receives shard ``d``'s converged
    (G, n) label matrix restricted to the rows that shard *owns* (see
    ``ownership_mask``) — this is where per-shard ``ColumnFingerprints``
    accumulate.  ``on_shard_mask(d, mask, srcs)`` receives the matching
    bool fill masks (all rows — ``PatternCollector.update`` is idempotent)
    for streaming the sparse CSC pattern.  Every per-source fixpoint is
    *identical* to the single-device driver's (the fixpoint is unique and
    chunking-independent), so counts, fingerprints, and patterns are
    bitwise-equal to ``run_multisource`` at any device count.

    ``on_progress(done, total, eta_s)`` (optional) fires after every
    sharded chunk step with a rolling-rate ETA — the same callback shape
    ``run_multisource`` takes, surfaced as ``analyze(on_progress=...)``.

    The loop is **double-buffered**: step k+1 is dispatched (JAX dispatch
    is async) before chunk k's host reduction runs, so fingerprint/pattern
    accumulation hides behind the next device step.  Chunks are reduced
    strictly in submission order, so delivery — and therefore every output
    — is bitwise-identical to the synchronous loop; the hidden reduction
    wall-time is reported as ``result.dist["overlap_hidden_s"]`` and the
    ``overlap.hidden_s`` counter (an ``overlap`` span wraps each hidden
    reduction when tracing).

    Returns a ``core.multisource.MultiSourceResult`` plus a ``stats`` dict
    (per-device edge checks, balance ratio) attached as ``result.dist``.
    """
    from repro.core.multisource import MultiSourceResult

    if axes is None:
        axes = tuple(mesh.axis_names)
    n = graph.n
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    srcs_mat = assign_sources(n, n_shards, policy=policy)   # (D, per)
    owned = ownership_mask(srcs_mat)
    per = srcs_mat.shape[1]
    concurrency = max(1, min(concurrency, per))
    with _ot.span("shard_step_build"):
        step = make_distributed_chunk_step(mesh, n, backend=backend,
                                           axes=axes)

    l_counts = np.zeros(n, dtype=np.int64)
    u_counts = np.zeros(n, dtype=np.int64)
    edge_checks = np.zeros(n, dtype=np.int64)
    conv_iters = np.zeros(n, dtype=np.int64)
    per_dev_edges = np.zeros(n_shards, dtype=np.int64)
    supersteps = 0
    n_chunks = 0

    total_steps = -(-per // concurrency)
    meter = _om.ProgressMeter(on_progress) if on_progress is not None else None

    def _inputs(start):
        cols = srcs_mat[:, start:start + concurrency]
        own = owned[:, start:start + concurrency]
        if cols.shape[1] < concurrency:
            # fixed step shape: pad by repeating each shard's last column
            # (duplicate sources are idempotent and never owned twice)
            short = concurrency - cols.shape[1]
            cols = np.concatenate(
                [cols, np.repeat(cols[:, -1:], short, axis=1)], axis=1)
            own = np.concatenate(
                [own, np.zeros((n_shards, short), dtype=bool)], axis=1)
        return cols, own

    def _reduce(cols, own, outs):
        nonlocal supersteps, n_chunks
        labels, mask, l_cnt, u_cnt, edges, iters = _ot.fetch(
            tuple(outs), "shard chunk outputs")
        with _ot.span("host_reduce"):
            for d in range(n_shards):
                keep = own[d]
                srcs_d = cols[d][keep]
                l_counts[srcs_d] = l_cnt[d][keep]
                u_counts[srcs_d] = u_cnt[d][keep]
                edge_checks[srcs_d] = edges[d][keep]
                per_dev_edges[d] += int(edges[d][keep].sum())
                if on_shard_chunk is not None and keep.any():
                    on_shard_chunk(d, labels[d][keep], srcs_d)
                if on_shard_mask is not None:
                    on_shard_mask(d, mask[d], cols[d])
        # per-shard while_loop trip counts differ by design; the step's
        # wall-clock is the slowest shard's count
        supersteps += int(iters.max())
        n_chunks += 1
        if _ot.ENABLED:
            _om.registry().observe("fixpoint.iterations", int(iters.max()))
            _om.registry().count("fixpoint.chunks")
        if meter is not None:
            meter.update(n_chunks, total_steps)

    # double-buffered fixpoint: dispatch step k+1 (async JAX dispatch keeps
    # the devices busy) *before* consuming step k's outputs, so the host-side
    # fingerprint/pattern reduction of chunk k overlaps the device compute of
    # chunk k+1.  Chunks are still reduced strictly in order, so every
    # collector sees the exact same delivery sequence as the synchronous loop
    # — the bitwise conformance contract is untouched.
    pending = None
    overlap_hidden = 0.0
    for start in range(0, per, concurrency):
        with _ot.span("fixpoint_chunk"):
            cols, own = _inputs(start)
            # one sharded program; its first call in each analyze traces,
            # lowers and loads it
            with _ot.span("chunk_dispatch"):
                outs = step(_ot.put(cols, "chunk sources"), graph)
        if pending is not None:
            t0 = time.perf_counter()
            with _ot.span("overlap"):
                _reduce(*pending)
            overlap_hidden += time.perf_counter() - t0
        pending = (cols, own, outs)
    if pending is not None:
        _reduce(*pending)       # the last chunk has nothing left to hide it
    if _ot.ENABLED:
        _om.registry().count("overlap.hidden_s", overlap_hidden)

    result = MultiSourceResult(
        l_counts=l_counts, u_counts=u_counts, edge_checks=edge_checks,
        conv_iters=conv_iters, supersteps=supersteps, n_chunks=n_chunks,
        concurrency=concurrency, reinits=n_chunks, windows=n_chunks)
    balance = (float(per_dev_edges.max()) / max(1.0, float(per_dev_edges.min()))
               if n_shards > 1 else 1.0)
    result.dist = {                                 # type: ignore[attr-defined]
        "n_shards": n_shards,
        "per_device_edge_checks": per_dev_edges,
        "balance_ratio": balance,
        "policy": policy,
        "overlap_hidden_s": overlap_hidden,
    }
    return result
