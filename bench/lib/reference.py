"""Plain references: symbolic LU by Gaussian elimination on the structure,
the supernode partition by its definition, and sparse direct solves.

Symbolic: eliminating vertex k adds entry (i, j) for every i > k with (i, k)
in the pattern and every j > k with (k, j) in it (no pivoting).  Rows and
columns are Python-int bitsets, so one elimination step is one OR per
entry of row k and column k.
"""
from __future__ import annotations

import dataclasses

import numpy as np
from scipy.sparse.linalg import splu

from bench.lib.patterns import Pattern


def _bits(x: int) -> np.ndarray:
    """Positions of the set bits of ``x``, ascending."""
    if x == 0:
        return np.zeros(0, dtype=np.int64)
    raw = np.frombuffer(x.to_bytes((x.bit_length() + 7) // 8, "little"),
                        dtype=np.uint8)
    return np.flatnonzero(np.unpackbits(raw, bitorder="little"))


@dataclasses.dataclass
class Symbolic:
    """Structure of L+U (diagonal included) of the unpivoted LU."""

    n: int
    indptr: np.ndarray     # CSC: column j's rows are
    rowind: np.ndarray     # rowind[indptr[j]:indptr[j + 1]]
    l_counts: np.ndarray   # per row: entries left of the diagonal
    u_counts: np.ndarray   # per row: entries right of the diagonal
    col_bits: list         # column bitsets (rows), for the supernode test

    @property
    def lu_nnz(self) -> int:
        return int(self.indptr[-1])


def symbolic_lu(p: Pattern) -> Symbolic:
    n = p.n
    row_bits = [0] * n
    col_bits = [0] * n
    rows = p.rows()
    for i, j in zip(rows.tolist(), p.indices.tolist()):
        row_bits[i] |= 1 << j
        col_bits[j] |= 1 << i
    for k in range(n):
        lk = col_bits[k] >> (k + 1)
        uk = row_bits[k] >> (k + 1)
        if not lk or not uk:
            continue
        ls, us = lk << (k + 1), uk << (k + 1)
        for i in (_bits(lk) + k + 1).tolist():
            row_bits[i] |= us
        for j in (_bits(uk) + k + 1).tolist():
            col_bits[j] |= ls
    cols = [_bits(c) for c in col_bits]
    indptr = np.zeros(n + 1, dtype=np.int64)
    indptr[1:] = np.cumsum([len(c) for c in cols])
    l_counts = np.array([(r & ((1 << i) - 1)).bit_count()
                         for i, r in enumerate(row_bits)], dtype=np.int64)
    u_counts = np.array([(r >> (i + 1)).bit_count()
                         for i, r in enumerate(row_bits)], dtype=np.int64)
    return Symbolic(n=n, indptr=indptr, rowind=np.concatenate(cols),
                    l_counts=l_counts, u_counts=u_counts, col_bits=col_bits)


def supernodes(sym: Symbolic, *, max_size: int) -> np.ndarray:
    """(S, 2) [start, end) fundamental supernodes: columns j-1 and j share
    one iff L(j, j-1) is an entry and their structures agree on rows >= j.
    Each maximal run is cut into ``max_size``-column pieces from its start."""
    c = sym.col_bits
    join = [False] + [(c[j - 1] >> j) & 1 == 1 and c[j - 1] >> j == c[j] >> j
                      for j in range(1, sym.n)]
    starts = [j for j in range(sym.n) if not join[j]]
    out = []
    for s, e in zip(starts, starts[1:] + [sym.n]):
        for a in range(s, e, max_size):
            out.append((a, min(a + max_size, e)))
    return np.array(out, dtype=np.int64).reshape(-1, 2)


def residual(p: Pattern, values: np.ndarray, x: np.ndarray,
             b: np.ndarray) -> float:
    """Relative 2-norm residual ||b - A x|| / ||b||, in float64."""
    r = b - p.scipy(values) @ np.asarray(x, dtype=np.float64)
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def solve(p: Pattern, values: np.ndarray, b: np.ndarray, *,
          dtype=np.float64) -> np.ndarray:
    """Direct sparse solve (SuperLU, COLAMD order) in ``dtype``; float32 is
    the control: the next precision below the configuration's float64."""
    a = p.scipy(values.astype(dtype)).tocsc()
    return splu(a).solve(b.astype(dtype)).astype(np.float64)
