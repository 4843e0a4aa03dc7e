"""Operations and bytes that a kernel's work needs, from shapes alone.

Both counts are of the work itself, before any padding to tiles: padding
is time the kernel spends, so it lowers the roofline share, as it should.
"""
from __future__ import annotations

import numpy as np


def fingerprint_bytes(n: int, n_chunks: int) -> float:
    """HBM bytes of one analyze's supernode-fingerprint passes: every source
    row of int32 labels read once (n sources x n columns), four int32 lanes
    of per-source meta (source id, two hashes, a valid flag), and three
    int32 partials per column written per chunk."""
    return 4.0 * n * n + 16.0 * n + 12.0 * n * n_chunks


def panel_gemm_work(indptr: np.ndarray, rowind: np.ndarray,
                    supernodes: np.ndarray):
    """(flops, bytes) of the left-looking panel GEMMs ``X(s:, J) -= L(s:,
    anc) @ U(anc, J)``, one per supernode J = [s, e) that has ancestors.

    From the CSC L+U pattern: M = rows >= s in J's columns (the panel's
    diagonal block and its L rows), K = rows < s in J's columns (the
    ancestor rows of U(:, J)), N = e - s.  2*M*K*N flops; float32 bytes of
    reading L (M, K), U (K, N) and the target (M, N), and writing it."""
    flops = 0.0
    nbytes = 0.0
    for s, e in np.asarray(supernodes).tolist():
        rows = np.unique(rowind[indptr[s]:indptr[e]])
        k = int(np.searchsorted(rows, s))
        if k == 0:
            continue
        m, w = len(rows) - k, e - s
        flops += 2.0 * m * k * w
        nbytes += 4.0 * (m * k + k * w + 2 * m * w)
    return flops, nbytes
