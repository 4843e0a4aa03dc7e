"""Sparsity patterns, values and right-hand sides, all made from a seed.

A pattern is ``Pattern(n, indptr, indices)``: structural CSR with sorted,
deduplicated column ids and every diagonal entry present.  Seeds may be any
non-negative integer, also above 32 bits: values, jitter and right-hand
sides draw from ``numpy.random.default_rng([seed, stream, step + 1])``, a
pattern from ``default_rng(seed)``.

An analyze unit gets a pattern of its own: the configuration's pattern
under a labelling drawn from the seed and the unit (``Relabeller``), of the
same size and the same elimination work, so that a plan made for one unit
is wrong for the next.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import reverse_cuthill_mckee

GENERATORS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "generators")

# stream ids: one independent random stream per use of the seed
PATTERN, VALUES, JITTER, RHS, RELABEL = 0, 1, 2, 3, 4


@dataclasses.dataclass(frozen=True)
class Pattern:
    n: int
    indptr: np.ndarray    # (n+1,) int64
    indices: np.ndarray   # (nnz,) int32, sorted within each row

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    def rows(self) -> np.ndarray:
        """(nnz,) row id of every entry."""
        return np.repeat(np.arange(self.n, dtype=np.int64),
                         np.diff(self.indptr))

    def scipy(self, values: np.ndarray) -> sp.csr_matrix:
        return sp.csr_matrix((values, self.indices, self.indptr),
                             shape=(self.n, self.n))


def rng(seed: int, stream: int, step: int = 0) -> np.random.Generator:
    if stream == PATTERN:
        # one stream, seeded as the published generator seeds it, so that
        # seed 3 gives the bbd-20k pattern whose counts are on record
        return np.random.default_rng(int(seed))
    return np.random.default_rng([int(seed), stream, int(step) + 1])


def from_coo(n: int, rows, cols) -> Pattern:
    """Deduplicated row-sorted CSR of ``(rows, cols)`` plus the diagonal."""
    rows = np.concatenate([np.asarray(rows, np.int64), np.arange(n)])
    cols = np.concatenate([np.asarray(cols, np.int64), np.arange(n)])
    key = np.unique(rows * n + cols)
    r, c = key // n, key % n
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, r + 1, 1)
    return Pattern(n, np.cumsum(indptr), c.astype(np.int32))


def permute(p: Pattern, perm: np.ndarray) -> Pattern:
    """Symmetric permutation: new entry (i, j) is old (perm[i], perm[j])."""
    inv = np.empty_like(perm)
    inv[perm] = np.arange(p.n, dtype=perm.dtype)
    return from_coo(p.n, inv[p.rows()], inv[p.indices.astype(np.int64)])


def rcm(p: Pattern) -> np.ndarray:
    """Reverse Cuthill-McKee order of the symmetrized pattern."""
    s = p.scipy(np.ones(p.nnz, dtype=np.float32))
    sym = sp.csr_matrix(((s + s.T) > 0).astype(np.float32))
    return np.asarray(reverse_cuthill_mckee(sym, symmetric_mode=True),
                      dtype=np.int64)


ORDERINGS = {"natural": None, "rcm": rcm}


def _natural(cfg: dict, seed: int) -> Pattern:
    """``generators/<generator>.py``'s ``make(seed=..., **args)``."""
    path = os.path.join(GENERATORS, f"{cfg['generator']}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_generator_{cfg['generator']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.make(seed=seed, **cfg["args"])


def _ordered(cfg: dict, p: Pattern) -> Pattern:
    order = ORDERINGS[cfg["ordering"]]
    return p if order is None else permute(p, order(p))


def generate(cfg: dict, seed: int) -> Pattern:
    """The configuration's pattern: its generator's, then its ordering."""
    return _ordered(cfg, _natural(cfg, seed))


def elimination_forest(p: Pattern) -> np.ndarray:
    """(n,) parent of each vertex in the elimination forest of A + A^T, -1
    at a root (Liu's algorithm with path compression)."""
    s = p.scipy(np.ones(p.nnz, dtype=np.float32))
    s = sp.csr_matrix((s + s.T) > 0)
    parent = np.full(p.n, -1, dtype=np.int64)
    anc = np.full(p.n, -1, dtype=np.int64)
    for i in range(p.n):
        for k in s.indices[s.indptr[i]:s.indptr[i + 1]].tolist():
            while k < i and anc[k] not in (-1, i):
                anc[k], k = i, anc[k]
            if k < i and anc[k] == -1:
                anc[k] = parent[k] = i
    return parent


def sibling_subtrees(p: Pattern) -> list:
    """``[(size, starts)]``: label ranges ``[start, start + size)`` that are
    whole subtrees of the elimination forest, of one size and one parent,
    and not nested in another listed range.

    Laying such ranges out in another order keeps every vertex after its
    descendants, so the fill is the same up to the labels; no range ends
    right before its parent, so no supernode is joined or split."""
    parent = elimination_forest(p)
    size = np.ones(p.n, dtype=np.int64)
    lo = np.arange(p.n)
    for v in range(p.n):
        if parent[v] >= 0:
            size[parent[v]] += size[v]
            lo[parent[v]] = min(lo[parent[v]], lo[v])
    groups = {}
    for v in np.flatnonzero(np.arange(p.n) - lo + 1 == size).tolist():
        if parent[v] != v + 1:
            groups.setdefault((int(parent[v]), int(size[v])), []).append(
                int(lo[v]))
    covered = np.zeros(p.n, dtype=bool)
    out = []
    for (_, n_v), starts in sorted(groups.items(), key=lambda kv: -kv[0][1]):
        starts = np.array(starts, dtype=np.int64)
        if len(starts) < 2 or covered[starts].any():
            continue
        for s0 in starts.tolist():
            covered[s0:s0 + n_v] = True
        out.append((n_v, starts))
    return out


class Relabeller:
    """The configuration's pattern under a labelling per unit of work,
    drawn from ``(seed, unit)``; ``cfg["relabel"]`` says how:

    * ``sibling_subtrees``: whole subtrees of the elimination forest that
      share a parent and a size change places (``sibling_subtrees``): the
      same fill, supernodes and shapes under new labels;
    * ``before_ordering``: the generator's pattern is shuffled at random
      before the configuration's ordering, which then picks its own start
      and ties.
    """

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.seed, self.how = cfg, seed, cfg["relabel"]
        self.natural = _natural(cfg, seed)
        self.base = _ordered(cfg, self.natural)
        if self.how == "sibling_subtrees":
            self.groups = sibling_subtrees(self.base)
        elif self.how != "before_ordering":
            raise ValueError(f"unknown relabel {self.how!r}")

    def __call__(self, unit: int) -> Pattern:
        g = rng(self.seed, RELABEL, unit)
        if self.how == "before_ordering":
            shuffled = permute(self.natural, g.permutation(self.natural.n))
            return _ordered(self.cfg, shuffled)
        perm = np.arange(self.base.n)
        for n_v, starts in self.groups:
            step = np.arange(n_v)
            perm[(starts[:, None] + step).ravel()] = (
                g.permutation(starts)[:, None] + step).ravel()
        return permute(self.base, perm)


def base_values(p: Pattern, seed: int) -> np.ndarray:
    """CSR-aligned values: off-diagonals uniform on [0.5, 1.5], each
    diagonal one more than its row's off-diagonal sum (strictly dominant)."""
    v = rng(seed, VALUES).uniform(0.5, 1.5, size=p.nnz)
    rows = p.rows()
    diag = rows == p.indices
    v[diag] = 0.0
    v[diag] = np.bincount(rows, weights=v, minlength=p.n) + 1.0
    return v


def step_values(base: np.ndarray, seed: int, step: int,
                jitter: float) -> np.ndarray:
    """Newton step ``step``'s values: ``base * (1 + jitter * u)``, u uniform
    on [-1, 1] entrywise."""
    u = rng(seed, JITTER, step).uniform(-1.0, 1.0, size=base.shape)
    return base * (1.0 + jitter * u)


def rhs(n: int, seed: int, step: int) -> np.ndarray:
    return rng(seed, RHS, step).standard_normal(n)
