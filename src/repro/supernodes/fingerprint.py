"""Streaming per-column structure fingerprints for supernode detection.

The serial post-pass (core/symbolic.detect_supernodes) compares whole columns
of the *gathered dense* filled pattern — O(n^2) memory and a serial scan.
This module replaces the gather: because row ``i`` of the filled pattern is
exactly the converged label row of source ``i``, the below-diagonal structure
of every column of L can be summarized *incrementally* as the multi-source
driver streams per-chunk converged ``maxId`` matrices (DESIGN.md §3).  Per
column ``j`` we keep three O(n) accumulators:

    counts[j] = |{ i > j : filled(i, j) }|         (below-diagonal nnz)
    hsum[j]   = sum_{i in that set} mix1(i)        (mod 2^32)
    hxor[j]   = xor_{i in that set} mix2(i)

plus ``subdiag[j] = filled(j, j-1)`` (the L(j, j-1) != 0 half of the T2
test).  All three column reductions are associative and commutative, so
chunks can arrive in any order, with any width (bubble-removal chunks are
narrower than n — they simply touch fewer columns), under any label-window
offset, and partial accumulators from disjoint source shards merge exactly
(multi-device detection composes with core/distributed.py source sharding).

Two independent 32-bit row hashes + the exact count make a fingerprint
collision (two different column structures comparing equal) a < 2^-64-ish
event per column pair; detect.py documents the probabilistic contract.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as _om
from repro.obs import trace as _ot

_GOLDEN = np.uint64(2654435761)          # Knuth multiplicative hash
_MASK32 = np.uint64(0xFFFFFFFF)


def mix1(ids: np.ndarray) -> np.ndarray:
    """Multiplicative row hash, uint32 (wrapping)."""
    x = (np.asarray(ids, dtype=np.uint64) + 1) * _GOLDEN
    return (x & _MASK32).astype(np.uint32)


def mix2(ids: np.ndarray) -> np.ndarray:
    """murmur3 fmix32 row hash — independent of mix1."""
    x = (np.asarray(ids, dtype=np.uint64) + 1) & _MASK32
    x ^= x >> 16
    x = (x * np.uint64(0x85EBCA6B)) & _MASK32
    x ^= x >> 13
    x = (x * np.uint64(0xC2B2AE35)) & _MASK32
    x ^= x >> 16
    return x.astype(np.uint32)


@dataclasses.dataclass
class ColumnFingerprints:
    """O(n) fingerprint state, filled row-chunk by row-chunk.

    ``update`` consumes a converged label matrix exactly as multisource emits
    it (possibly width-truncated, offset-encoded, and padded with repeated
    sources); rows already seen are ignored, so re-delivery (chunk padding,
    checkpoint replay) is idempotent.
    """

    n: int
    backend: str = "auto"        # "kernel" (Pallas), "ref" (jnp), "auto"

    def __post_init__(self):
        self.counts = np.zeros(self.n, dtype=np.int64)
        self.hsum = np.zeros(self.n, dtype=np.uint32)
        self.hxor = np.zeros(self.n, dtype=np.uint32)
        self.subdiag = np.zeros(self.n, dtype=bool)
        self.seen = np.zeros(self.n, dtype=bool)

    @property
    def complete(self) -> bool:
        return bool(self.seen.all())

    def update(self, labels: jax.Array, srcs: np.ndarray,
               offset: int = 0) -> int:
        """Accumulate one converged chunk; returns #new rows consumed.

        labels: (G, W) int32 ``offset + maxId`` label matrix, W <= n
                (bubble-removal chunks are narrower; a source s < W only ever
                contributes to columns j < s < W, so truncation is lossless).
        srcs:   (G,) source ids of the label rows (repeats allowed — padding).
        """
        if not _ot.SPANS:
            return self._update(labels, srcs, offset)
        t0 = time.perf_counter()
        with _ot.span("fingerprint_update"):
            consumed = self._update(labels, srcs, offset)
        if not _ot.ENABLED:
            return consumed
        # analytic traffic of the column reduction: the (consumed, W) int32
        # label block read once + the three W-wide int32 partials written
        reg = _om.registry()
        reg.count("fingerprint.seconds", time.perf_counter() - t0)
        reg.count("fingerprint.bytes",
                  4 * consumed * labels.shape[1] + 12 * labels.shape[1])
        return consumed

    def _update(self, labels: jax.Array, srcs: np.ndarray,
                offset: int = 0) -> int:
        srcs = np.asarray(srcs, dtype=np.int64)
        w = labels.shape[1]
        # first occurrence within the batch, then drop rows seen earlier
        _, first = np.unique(srcs, return_index=True)
        keep = first[~self.seen[srcs[first]]]
        if len(keep) == 0:
            return 0
        kept_srcs = srcs[keep]
        self.seen[kept_srcs] = True

        lab = _ot.put(labels, "chunk labels")[jnp.asarray(keep,
                                                          dtype=jnp.int32)]
        off = jnp.int32(offset)
        # offset-free labels: maxId, or w+1 (> any real column) when the
        # label is uninitialized / stale arena garbage
        rel = jnp.where(lab <= off + jnp.int32(w), lab - off, jnp.int32(w) + 1)

        src_j = jnp.asarray(kept_srcs, dtype=jnp.int32)
        m1 = jnp.asarray(mix1(kept_srcs).view(np.int32))
        m2 = jnp.asarray(mix2(kept_srcs).view(np.int32))
        valid = jnp.ones((len(keep),), dtype=jnp.int32)

        from repro.kernels import ops as kops
        if self.backend == "ref":
            part = kops.column_fingerprints_ref(rel, src_j, m1, m2, valid)
        elif self.backend == "kernel":
            part = kops.column_fingerprints(rel, src_j, m1, m2, valid)
        else:  # auto: the Pallas kernel on real TPU, the jnp oracle elsewhere
            if jax.default_backend() == "tpu":
                part = kops.column_fingerprints(rel, src_j, m1, m2, valid)
            else:
                part = kops.column_fingerprints_ref(rel, src_j, m1, m2, valid)
        part = _ot.fetch(part, "fingerprint partials")
        self.counts[:w] += part[0].astype(np.int64)
        self.hsum[:w] += part[1].view(np.uint32)
        self.hxor[:w] ^= part[2].view(np.uint32)

        # subdiag half of T2: filled(s, s-1) <=> maxId[s-1] < s-1
        has_prev = kept_srcs >= 1
        if np.any(has_prev):
            rows = np.flatnonzero(has_prev)
            cols = kept_srcs[rows] - 1
            vals = _ot.fetch(rel[jnp.asarray(rows, jnp.int32),
                                 jnp.asarray(cols, jnp.int32)],
                             "subdiagonal labels")
            self.subdiag[kept_srcs[rows]] = vals < cols
        return len(keep)

    def merge(self, other: "ColumnFingerprints") -> "ColumnFingerprints":
        """Fold a disjoint shard's partial fingerprints into this one
        (multi-device detection: each shard accumulates its own sources,
        partials merge associatively at the host)."""
        assert self.n == other.n
        overlap = self.seen & other.seen
        if overlap.any():
            raise ValueError(
                f"cannot merge overlapping fingerprint shards: rows "
                f"{np.flatnonzero(overlap)[:8].tolist()}... seen on both sides")
        self.counts += other.counts
        self.hsum += other.hsum
        self.hxor ^= other.hxor
        self.subdiag |= other.subdiag
        self.seen |= other.seen
        return self


def fingerprints_from_graph(graph, *, concurrency: int = 128,
                            backend: str = "ell", bubble: bool = False,
                            use_arena: bool = True,
                            fp_backend: str = "auto") -> ColumnFingerprints:
    """Convenience: run the multi-source fixpoint purely to collect
    fingerprints (symbolic_factorize(detect_supernodes=True) gets them for
    free from the same pass)."""
    from repro.core.multisource import run_multisource

    fp = ColumnFingerprints(n=graph.n, backend=fp_backend)
    run_multisource(graph, concurrency=concurrency, backend=backend,
                    bubble=bubble, use_arena=use_arena, on_chunk=fp.update)
    return fp
