"""End-to-end sparse solve on the packed supernodal factors (DESIGN.md §9).

``solve(a, b)`` closes the loop the symbolic phase opens: predict the fill,
factor in O(nnz(L+U)) packed panel storage (``supernodal.numeric_factorize``),
then run supernodal forward/backward triangular substitution over the packed
blocks plus iterative refinement:

* **Forward** (L y = b, unit diagonal): panels ascending — solve the packed
  diagonal block against y[s:e], then push ``y[below] -= L(below, J) @ y[s:e]``
  using the panel's below-diagonal rows.
* **Backward** (U x = y): panels descending — solve the upper-triangular
  diagonal block, then pull ``y[above] -= U(above, J) @ x[s:e]`` through the
  panel's above-diagonal (ancestor U) rows.
* **Level schedules** — substitution has its own dependency DAGs, *not* the
  factorization's: forward panel J waits on every panel whose below rows land
  in J's columns (L structure); backward is the reverse of the factorization's
  U-ancestor DAG.  ``build_solve_schedule`` levels both: a panel's diagonal
  *solve* never reads same-level data, so the solves within a level are
  independent (the batch/placement unit).  Their scatter pushes into later
  panels' rows may overlap, though — a parallel within-level implementation
  must combine them (segmented reduction / atomics); this serial sweep
  applies them in panel order.
* **Iterative refinement** — r = b - A x via the O(nnz) CSR matvec,
  re-solve on the factors, accept only improving corrections, so the
  recorded relative-residual history is non-increasing by construction.

Everything here reads the packed blocks; nothing materializes (n, n).
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import numpy as np
from scipy.linalg import solve_triangular

from repro.numeric.storage import BatchedPanelStore, PanelStore
from repro.numeric.supernodal import (
    BatchedNumericResult, NumericResult, numeric_factorize,
)
from repro.obs import trace as _ot
from repro.sparse.csr import CSRMatrix
from repro.sparse.numeric import csr_matvec, generic_values_csr


@dataclasses.dataclass
class SolveSchedule:
    """Dependency levels of the two substitution sweeps (panel ids per
    level, execution order: forward ascending, backward descending)."""

    fwd_levels: List[np.ndarray]
    bwd_levels: List[np.ndarray]

    @property
    def n_fwd_levels(self) -> int:
        return len(self.fwd_levels)

    @property
    def n_bwd_levels(self) -> int:
        return len(self.bwd_levels)


def build_solve_schedule(store: PanelStore) -> SolveSchedule:
    """Level both substitution DAGs from the packed row structure.

    Forward: K -> J iff panel K has below-diagonal rows inside J's column
    range (L block).  Backward: J -> K (J later) iff panel J has
    above-diagonal rows inside K's range (U block) — the reverse of the
    factorization's ancestor relation.
    """
    k = store.n_panels
    fwd = np.zeros(k, dtype=np.int64)
    bwd = np.zeros(k, dtype=np.int64)
    for j in range(k):
        s, e = store.supernodes[j]
        d = int(store.diag[j])
        w = e - s
        below = store.rows[j][d + w:]
        if len(below):
            tgt = np.unique(store.sup_of_col[below])
            fwd[tgt] = np.maximum(fwd[tgt], fwd[j] + 1)
    for j in range(k - 1, -1, -1):
        above = store.rows[j][:store.diag[j]]
        if len(above):
            tgt = np.unique(store.sup_of_col[above])
            bwd[tgt] = np.maximum(bwd[tgt], bwd[j] + 1)
    fwd_levels = [np.flatnonzero(fwd == lv)
                  for lv in range(int(fwd.max()) + 1 if k else 0)]
    bwd_levels = [np.flatnonzero(bwd == lv)
                  for lv in range(int(bwd.max()) + 1 if k else 0)]
    return SolveSchedule(fwd_levels=fwd_levels, bwd_levels=bwd_levels)


def _solve_schedule_of(store: PanelStore) -> SolveSchedule:
    sched = getattr(store, "_solve_schedule", None)
    if sched is None:
        sched = build_solve_schedule(store)
        store._solve_schedule = sched
    return sched


def _placement_of(store: PanelStore):
    return getattr(store, "_placement", None)


def _level_iter(store: PanelStore, level: np.ndarray):
    """Per-device segments of one level (the parallel dispatch unit,
    DESIGN.md §11) — a single all-panels segment without a placement.
    Diagonal solves within a level are independent and write disjoint
    ranges, so segment grouping never changes a float op."""
    placement = _placement_of(store)
    if placement is None or placement.n_devices <= 1:
        return (level,)
    return tuple(seg for seg in placement.segments(level) if len(seg))


def _batched_solve_unit_lower(mats: np.ndarray, rhs: np.ndarray) -> np.ndarray:
    """Forward substitution vmapped over stacked panels: ``mats`` (p, w, w)
    L\\U-packed unit-lower blocks against ``rhs`` (p, w, k), in place.
    One batched row-sweep per level-width group replaces p * k scalar
    triangular solves — numpy broadcasting is the vmap."""
    w = mats.shape[1]
    for i in range(1, w):
        rhs[:, i, :] -= np.einsum("pj,pjk->pk", mats[:, i, :i], rhs[:, :i, :])
    return rhs


def _batched_solve_upper(mats: np.ndarray, rhs: np.ndarray) -> np.ndarray:
    """Backward substitution vmapped over stacked panels (non-unit upper)."""
    w = mats.shape[1]
    for i in range(w - 1, -1, -1):
        if i + 1 < w:
            rhs[:, i, :] -= np.einsum("pj,pjk->pk", mats[:, i, i + 1:],
                                      rhs[:, i + 1:, :])
        rhs[:, i, :] /= mats[:, i, i][:, None]
    return rhs


def _diag_block(store: PanelStore, j: int) -> np.ndarray:
    s, e = store.supernodes[j]
    d = int(store.diag[j])
    return store.blocks[j][d:d + e - s]


def _level_diag_solves(store: PanelStore, level: np.ndarray, y: np.ndarray,
                       *, lower: bool, batched: bool) -> None:
    """Phase 1 of one substitution level: every panel's diagonal solve.

    ``batched=True`` groups the level's panels by width and runs ONE
    vmapped solve per group (multi-RHS ``y`` solves all columns in the
    same call); otherwise panels are walked per device segment with scipy
    BLAS.  Either way the solves are independent and touch disjoint
    ``y[s:e]`` ranges, so results do not depend on grouping or device
    count — only on which algorithm (batched sweep vs LAPACK trsm) ran.
    """
    widths = (store.supernodes[level, 1] - store.supernodes[level, 0])
    if batched:
        multi = y.ndim == 2
        for w in np.unique(widths):
            ids = level[widths == w]
            if not lower:          # scalar division handles w == 1 upper
                if w == 1:
                    diag = np.array([_diag_block(store, int(j))[0, 0]
                                     for j in ids])
                    starts = store.supernodes[ids, 0]
                    y[starts] = (y[starts].T / diag).T
                    continue
            if w == 1:
                continue           # unit lower: nothing to solve
            mats = np.stack([_diag_block(store, int(j)) for j in ids])
            rhs = np.stack([y[s:e] for s, e in store.supernodes[ids]])
            if not multi:
                rhs = rhs[:, :, None]
            rhs = (_batched_solve_unit_lower(mats, rhs) if lower
                   else _batched_solve_upper(mats, rhs))
            for i, (s, e) in enumerate(store.supernodes[ids]):
                y[s:e] = rhs[i] if multi else rhs[i, :, 0]
        return
    for seg in _level_iter(store, level):
        for j in seg:
            s, e = store.supernodes[j]
            w = e - s
            diag = _diag_block(store, int(j))
            if lower:
                if w > 1:
                    y[s:e] = solve_triangular(diag, y[s:e], lower=True,
                                              unit_diagonal=True,
                                              check_finite=False)
            else:
                if w == 1:
                    y[s] = y[s] / diag[0, 0]
                else:
                    y[s:e] = solve_triangular(diag, y[s:e], lower=False,
                                              check_finite=False)


def forward_substitute(store: PanelStore, b: np.ndarray, *,
                       batched: Optional[bool] = None) -> np.ndarray:
    """y with L y = b (unit-lower L in the packed blocks).

    Each level runs in two phases: the independent diagonal solves
    (grouped per device segment, or batched into one vmapped call per
    level-width group — ``batched=None`` auto-enables batching for
    multi-RHS ``b``), then the scatter pushes applied in ascending panel
    order.  Pushes from same-level panels may overlap on later rows, so
    the ascending application order is the deterministic combine that
    keeps results bitwise-identical at every device count.
    """
    y = np.asarray(b, dtype=np.float64).copy()
    if batched is None:
        batched = y.ndim == 2
    with _ot.span("solve_forward"):
        for level in _solve_schedule_of(store).fwd_levels:
            with _ot.span("fwd_level"):
                _level_diag_solves(store, level, y, lower=True,
                                   batched=batched)
                for j in level:               # ascending: fwd_levels sorted
                    s, e = store.supernodes[j]
                    d = int(store.diag[j])
                    below = store.rows[j][d + (e - s):]
                    if len(below):
                        y[below] -= store.blocks[j][d + (e - s):] @ y[s:e]
    return y


def backward_substitute(store: PanelStore, y: np.ndarray, *,
                        batched: Optional[bool] = None) -> np.ndarray:
    """x with U x = y (upper U in the packed blocks); same two-phase level
    structure as ``forward_substitute``."""
    x = np.asarray(y, dtype=np.float64).copy()
    if batched is None:
        batched = x.ndim == 2
    with _ot.span("solve_backward"):
        for level in _solve_schedule_of(store).bwd_levels:
            with _ot.span("bwd_level"):
                _level_diag_solves(store, level, x, lower=False,
                                   batched=batched)
                for j in level:
                    s, e = store.supernodes[j]
                    above = store.rows[j][:store.diag[j]]
                    if len(above):
                        x[above] -= store.blocks[j][:store.diag[j]] @ x[s:e]
    return x


def solve_factored(num: NumericResult, b: np.ndarray, *,
                   batched: Optional[bool] = None) -> np.ndarray:
    """x = U^{-1} L^{-1} b on the packed factors (no refinement)."""
    return backward_substitute(num.store,
                               forward_substitute(num.store, b,
                                                  batched=batched),
                               batched=batched)


# -- transposed substitution (robust tier, DESIGN.md §15) --------------------
#
# Hager's 1-norm condition estimator needs A^{-T} applied to a vector, which
# the packed factors give as L^{-T} U^{-T}.  The sweeps mirror the primal
# ones with reading and writing roles swapped: L^T pulls a panel's own range
# from its *below* rows (owned by later panels, so a plain descending panel
# walk is topologically correct — once a panel's diagonal solve ran, nothing
# later writes its range), U^T pulls from the *above* rows (earlier panels,
# ascending walk).  These are diagnostic paths (a handful of solves per
# quality estimate), so they stay serial and unscheduled.


def backward_substitute_t(store: PanelStore, b: np.ndarray) -> np.ndarray:
    """x with L^T x = b (unit-lower L in the packed blocks, transposed)."""
    x = np.asarray(b, dtype=np.float64).copy()
    with _ot.span("solve_backward_t"):
        for j in range(store.n_panels - 1, -1, -1):
            s, e = store.supernodes[j]
            w = e - s
            d = int(store.diag[j])
            below = store.rows[j][d + w:]
            if len(below):
                x[s:e] -= store.blocks[j][d + w:].T @ x[below]
            if w > 1:
                x[s:e] = solve_triangular(store.blocks[j][d:d + w], x[s:e],
                                          lower=True, unit_diagonal=True,
                                          trans="T", check_finite=False)
    return x


def forward_substitute_t(store: PanelStore, b: np.ndarray) -> np.ndarray:
    """w with U^T w = b (upper U in the packed blocks, transposed)."""
    y = np.asarray(b, dtype=np.float64).copy()
    with _ot.span("solve_forward_t"):
        for j in range(store.n_panels):
            s, e = store.supernodes[j]
            w = e - s
            d = int(store.diag[j])
            above = store.rows[j][:d]
            if len(above):
                y[s:e] -= store.blocks[j][:d].T @ y[above]
            diag = store.blocks[j][d:d + w]
            if w == 1:
                y[s] = y[s] / diag[0, 0]
            else:
                y[s:e] = solve_triangular(diag, y[s:e], lower=False,
                                          trans="T", check_finite=False)
    return y


def solve_factored_transposed(num: NumericResult, b: np.ndarray) -> np.ndarray:
    """z = A^{-T} b = L^{-T} U^{-T} b on the packed factors."""
    return backward_substitute_t(num.store,
                                 forward_substitute_t(num.store, b))


@dataclasses.dataclass
class SolveResult:
    """Solution + convergence history of one ``solve`` call.

    Timing is split so factorization is never conflated with substitution:
    ``factor_s`` is the numeric factorization built *by this call* (0.0 when
    a prebuilt ``num`` was reused), ``solve_s`` the substitution +
    refinement sweeps.  For multi-RHS solves ``x`` is (n, k) and each
    ``residuals`` entry is the worst (max) per-column relative residual.
    """

    x: np.ndarray
    residuals: List[float]       # relative 2-norm residuals: initial solve,
                                 # then after each *accepted* refinement
    num: NumericResult
    factor_s: float              # factorization time inside this call
    solve_s: float               # substitution + refinement time
    refine_accepted: int

    @property
    def residual(self) -> float:
        return self.residuals[-1]

    @property
    def elapsed_s(self) -> float:
        return self.factor_s + self.solve_s


def _col_residuals(matvec, x: np.ndarray, b: np.ndarray,
                   b_norms: np.ndarray) -> np.ndarray:
    """(k,) per-column relative 2-norm residuals ((1,) for vector RHS)."""
    r = b - matvec(x)
    if r.ndim == 1:
        return np.array([np.linalg.norm(r)]) / b_norms
    return np.linalg.norm(r, axis=0) / b_norms


def solve(a: CSRMatrix, b: np.ndarray, *, sym=None,
          values: Optional[np.ndarray] = None,
          pattern=None, supernodes: Optional[np.ndarray] = None,
          num: Optional[NumericResult] = None,
          refine_iters: int = 2, refine_tol: Optional[float] = None,
          n_bins: int = 8, policy: str = "lpt",
          backend: str = "numpy",
          batched: Optional[bool] = None,
          transform=None) -> SolveResult:
    """Solve A x = b through the symbolic -> packed-numeric -> substitution
    pipeline, with iterative refinement.

    ``b`` is a single right-hand side (n,) or a multi-RHS block (n, k) —
    the substitution sweeps and the refinement matvec are batched over the
    columns, so k systems cost one factorization plus k-column triangular
    solves (the circuit-simulation refactorization regime, DESIGN.md §10).
    ``batched`` picks the level-batched (vmapped) diagonal-solve path —
    ``None`` auto-enables it for multi-RHS ``b``; see
    ``forward_substitute``.

    ``a``/``sym``/``values``/``pattern``/``supernodes`` are forwarded to
    ``numeric_factorize`` (``values`` dense (n, n) or CSR-aligned (nnz,);
    defaults to ``generic_values_csr(a)``); pass ``num`` to reuse an
    existing factorization.  ``refine_iters`` bounds the refinement sweeps;
    a correction is accepted per column only if it lowers that column's
    relative residual, so the recorded (worst-column) ``residuals`` history
    is non-increasing; refinement stops early once every column is at or
    below ``refine_tol`` (default 1e-14 — a well-conditioned solve lands at
    machine precision immediately and skips the extra substitution + matvec
    sweeps; pass ``refine_tol=0.0`` to squeeze every accepted correction).

    ``transform`` (a ``repro.robust.RobustPlan``) wires the static-pivoting
    permutation/scalings around every inner factored solve (DESIGN.md §15):
    the factors are of ``A_f = Dr·P·A·Dc``, so each substitution runs on
    ``apply_rhs(rhs)`` and its result maps back through ``apply_solution``
    — while ``a``/``values``/``b`` stay the ORIGINAL system, which is what
    the refinement matvec iterates against.  ``None`` (default) leaves the
    float operations bitwise-identical to the historical path.

    Raises ``ZeroPivotError`` if the factorization hits a zero/near-zero
    pivot (propagated from ``numeric_factorize``).
    """
    t0 = time.perf_counter()
    b = np.asarray(b, dtype=np.float64)
    if (b.ndim not in (1, 2) or b.shape[0] != a.n
            or (b.ndim == 2 and b.shape[1] == 0)):
        raise ValueError(f"b must be ({a.n},) or ({a.n}, k>=1), "
                         f"got {b.shape}")
    if num is not None and values is None:
        # refinement computes residuals against `values`; silently defaulting
        # to generic values here would iterate against a different matrix
        # than the one `num` factored and corrupt the answer
        raise ValueError(
            "solve(num=...) needs the values the factorization was built "
            "from — pass the same `values` given to numeric_factorize")
    if values is None:
        values = generic_values_csr(a)
    values = np.asarray(values, dtype=np.float64)
    factor_s = 0.0
    if num is None:
        num = numeric_factorize(a, sym, values=values, pattern=pattern,
                                supernodes=supernodes, n_bins=n_bins,
                                policy=policy, backend=backend)
        factor_s = time.perf_counter() - t0

    if values.ndim == 2:
        def matvec(x):
            return values @ x
    else:
        def matvec(x):
            return csr_matvec(a, values, x)

    if refine_tol is None:
        refine_tol = 1e-14

    if transform is None:
        def fsolve(rhs):
            return solve_factored(num, rhs, batched=batched)
    else:
        def fsolve(rhs):
            return transform.apply_solution(
                solve_factored(num, transform.apply_rhs(rhs),
                               batched=batched))

    b_norms = (np.array([np.linalg.norm(b)]) if b.ndim == 1
               else np.linalg.norm(b, axis=0))
    b_norms = np.where(b_norms == 0.0, 1.0, b_norms)
    with _ot.span("solve"):
        x = fsolve(b)
        with _ot.span("residual"):
            res_cols = _col_residuals(matvec, x, b, b_norms)
        residuals = [float(res_cols.max())]
        accepted = 0
        for _ in range(max(0, refine_iters)):
            if res_cols.max() <= refine_tol:
                break
            with _ot.span("refine"):
                with _ot.span("residual"):
                    r = b - matvec(x)
                x_try = x + fsolve(r)
                with _ot.span("residual"):
                    res_try = _col_residuals(matvec, x_try, b, b_norms)
                improve = res_try < res_cols
                if not improve.any():
                    break              # no column improving — keep best x
                if x.ndim == 1:
                    x = x_try
                else:                  # accept only the improving columns
                    x[:, improve] = x_try[:, improve]
                res_cols = np.where(improve, res_try, res_cols)
                residuals.append(float(res_cols.max()))
                accepted += 1
    return SolveResult(x=x, residuals=residuals, num=num, factor_s=factor_s,
                       solve_s=time.perf_counter() - t0 - factor_s,
                       refine_accepted=accepted)


# -- batched-over-systems tier (DESIGN.md §14) ------------------------------
#
# Substitution over a ``BatchedPanelStore``: every per-panel push and scatter
# carries a leading system axis (stacked ``np.matmul`` / fancy indexing —
# per-slice bitwise-identical to the 2D forms), while the per-panel diagonal
# solves follow exactly the algorithm the sequential path would pick for ONE
# system of the same RHS shape: per-system LAPACK for vector RHS
# (``batched=False``), the width-grouped einsum sweeps stacked over systems
# for multi-RHS (``batched=True``).  System i of every result is therefore
# bitwise-identical to a loop of ``forward/backward_substitute`` /
# ``solve`` over the systems.


def _level_diag_solves_batch(bstore: BatchedPanelStore, level: np.ndarray,
                             y: np.ndarray, *, lower: bool) -> None:
    """Phase 1 of one substitution level for all B systems: ``y`` is
    (B, n) (per-system LAPACK solves, the sequential vector path) or
    (B, n, k) (width-grouped einsum sweeps with the systems stacked into
    the panel axis, the sequential multi-RHS path)."""
    store = bstore.template
    bsz = bstore.batch
    if y.ndim == 3:
        widths = (store.supernodes[level, 1] - store.supernodes[level, 0])
        for w in np.unique(widths):
            ids = level[widths == w]
            if not lower:
                if w == 1:
                    diag = np.stack(
                        [bstore.blocks[int(j)][:, int(store.diag[j]), 0]
                         for j in ids], axis=1)            # (B, p)
                    starts = store.supernodes[ids, 0]
                    y[:, starts] /= diag[:, :, None]
                    continue
            if w == 1:
                continue           # unit lower: nothing to solve
            # (B, p, w, .) stacked over systems -> (B*p, w, .): the einsum
            # row sweeps contract per (panel, column) slice, so deepening
            # the panel axis with the batch cannot change a float op
            mats = np.stack(
                [bstore.blocks[int(j)][:, int(store.diag[j]):
                                       int(store.diag[j]) + w]
                 for j in ids], axis=1)
            rhs = np.stack([y[:, s:e] for s, e in store.supernodes[ids]],
                           axis=1)
            k = y.shape[2]
            mats = mats.reshape(bsz * len(ids), w, w)
            rhs = rhs.reshape(bsz * len(ids), w, k)
            rhs = (_batched_solve_unit_lower(mats, rhs) if lower
                   else _batched_solve_upper(mats, rhs))
            rhs = rhs.reshape(bsz, len(ids), w, k)
            for pi, (s, e) in enumerate(store.supernodes[ids]):
                y[:, s:e] = rhs[:, pi]
        return
    for j in level:
        s, e = store.supernodes[j]
        w = e - s
        d = int(store.diag[j])
        if lower:
            if w > 1:
                for i in range(bsz):
                    y[i, s:e] = solve_triangular(
                        bstore.blocks[j][i, d:d + w], y[i, s:e], lower=True,
                        unit_diagonal=True, check_finite=False)
        else:
            if w == 1:
                y[:, s] = y[:, s] / bstore.blocks[j][:, d, 0]
            else:
                for i in range(bsz):
                    y[i, s:e] = solve_triangular(
                        bstore.blocks[j][i, d:d + w], y[i, s:e], lower=False,
                        check_finite=False)


def forward_substitute_batch(bstore: BatchedPanelStore,
                             b: np.ndarray) -> np.ndarray:
    """y with L_i y_i = b_i for every system i; ``b`` is (B, n) or
    (B, n, k)."""
    y = np.asarray(b, dtype=np.float64).copy()
    store = bstore.template
    with _ot.span("solve_forward"):
        for level in _solve_schedule_of(store).fwd_levels:
            with _ot.span("fwd_level"):
                _level_diag_solves_batch(bstore, level, y, lower=True)
                for j in level:               # ascending: fwd_levels sorted
                    s, e = store.supernodes[j]
                    d = int(store.diag[j])
                    below = store.rows[j][d + (e - s):]
                    if len(below):
                        blk = bstore.blocks[j][:, d + (e - s):]
                        if y.ndim == 2:
                            y[:, below] -= np.matmul(
                                blk, y[:, s:e, None])[..., 0]
                        else:
                            y[:, below] -= np.matmul(blk, y[:, s:e])
    return y


def backward_substitute_batch(bstore: BatchedPanelStore,
                              y: np.ndarray) -> np.ndarray:
    """x with U_i x_i = y_i for every system i."""
    x = np.asarray(y, dtype=np.float64).copy()
    store = bstore.template
    with _ot.span("solve_backward"):
        for level in _solve_schedule_of(store).bwd_levels:
            with _ot.span("bwd_level"):
                _level_diag_solves_batch(bstore, level, x, lower=False)
                for j in level:
                    s, e = store.supernodes[j]
                    above = store.rows[j][:store.diag[j]]
                    if len(above):
                        blk = bstore.blocks[j][:, :store.diag[j]]
                        if x.ndim == 2:
                            x[:, above] -= np.matmul(
                                blk, x[:, s:e, None])[..., 0]
                        else:
                            x[:, above] -= np.matmul(blk, x[:, s:e])
    return x


def solve_factored_batch(bnum: BatchedNumericResult,
                         b: np.ndarray) -> np.ndarray:
    """x_i = U_i^{-1} L_i^{-1} b_i on the batched packed factors (no
    refinement)."""
    return backward_substitute_batch(bnum.store,
                                     forward_substitute_batch(bnum.store, b))


@dataclasses.dataclass
class BatchedSolveResult:
    """Solutions + per-system convergence histories of one ``solve_batch``.

    ``x`` is (B, n) or (B, n, k); ``residuals[i]`` is system i's accepted
    worst-column relative-residual history (same per-system lengths and
    floats a loop of sequential ``solve`` calls would record);
    ``refine_accepted`` the (B,) accepted-correction counts.
    """

    x: np.ndarray
    residuals: List[List[float]]
    num: BatchedNumericResult
    solve_s: float
    refine_accepted: np.ndarray

    @property
    def batch(self) -> int:
        return self.num.batch

    @property
    def residual(self) -> np.ndarray:
        """(B,) final per-system worst-column relative residuals."""
        return np.array([h[-1] for h in self.residuals])

    def system(self, i: int) -> SolveResult:
        """System i repackaged as a sequential ``SolveResult`` (zero-copy
        factor view; ``factor_s``/``solve_s`` are not split per system)."""
        return SolveResult(x=self.x[i], residuals=list(self.residuals[i]),
                           num=self.num.system(i), factor_s=0.0,
                           solve_s=0.0,
                           refine_accepted=int(self.refine_accepted[i]))


def solve_batch(a: CSRMatrix, b: np.ndarray, values_batch: np.ndarray,
                bnum: BatchedNumericResult, *, refine_iters: int = 2,
                refine_tol: Optional[float] = None,
                transform=None) -> BatchedSolveResult:
    """Substitution + iterative refinement across all B factored systems at
    once: ``b`` is (B, n) or (B, n, k), ``values_batch`` the (B, nnz) value
    stack ``bnum`` was factored from (each system refines against its OWN
    matrix).

    Refinement runs the level sweeps over the whole batch each iteration
    and masks per system: a system leaves the active set exactly when the
    sequential loop would break (all columns at/below ``refine_tol``, or no
    column improving), corrections are accepted per (system, column) only
    when improving, and stopped systems' solutions are never touched — so
    every system's x, residual history, and accepted count are
    bitwise-identical to a loop of ``solve(..., num=num_i)`` calls.

    ``transform`` (a ``repro.robust.RobustPlan``) applies the
    static-pivoting permutation/scalings around the batched factored
    solves, exactly as in sequential ``solve``; ``a``/``values_batch``/``b``
    stay the original systems the refinement iterates against.
    """
    t0 = time.perf_counter()
    bsz = bnum.batch
    b = np.asarray(b, dtype=np.float64)
    n = bnum.n
    if (b.ndim not in (2, 3) or b.shape[0] != bsz or b.shape[1] != n
            or (b.ndim == 3 and b.shape[2] == 0)):
        raise ValueError(f"b must be ({bsz}, {n}) or ({bsz}, {n}, k>=1), "
                         f"got {b.shape}")
    values_batch = np.asarray(values_batch, dtype=np.float64)
    if values_batch.ndim != 2 or values_batch.shape[0] != bsz:
        raise ValueError(f"values_batch must be ({bsz}, nnz), got "
                         f"{values_batch.shape}")
    if refine_tol is None:
        refine_tol = 1e-14

    if transform is None:
        def fsolve(rhs):
            return solve_factored_batch(bnum, rhs)
    else:
        def fsolve(rhs):
            return transform.apply_solution_batch(
                solve_factored_batch(bnum, transform.apply_rhs_batch(rhs)))

    def residuals_of(x):
        # per-system _col_residuals (same norm calls as sequential solve)
        return np.stack([
            _col_residuals(lambda v: csr_matvec(a, values_batch[i], v),
                           x[i], b[i], b_norms[i]) for i in range(bsz)])

    b_norms = np.stack([
        np.array([np.linalg.norm(b[i])]) if b.ndim == 2
        else np.linalg.norm(b[i], axis=0) for i in range(bsz)])
    b_norms = np.where(b_norms == 0.0, 1.0, b_norms)

    with _ot.span("solve_batch"):
        x = fsolve(b)
        with _ot.span("residual"):
            res_cols = residuals_of(x)                   # (B, kk)
        histories = [[float(res_cols[i].max())] for i in range(bsz)]
        accepted = np.zeros(bsz, dtype=np.int64)
        stopped = np.zeros(bsz, dtype=bool)
        for _ in range(max(0, refine_iters)):
            at_tol = res_cols.max(axis=1) <= refine_tol
            active = ~stopped & ~at_tol
            stopped |= at_tol
            if not active.any():
                break
            with _ot.span("refine"):
                with _ot.span("residual"):
                    r = np.stack([b[i] - csr_matvec(a, values_batch[i], x[i])
                                  for i in range(bsz)])
                x_try = x + fsolve(r)
                with _ot.span("residual"):
                    res_try = residuals_of(x_try)
                improve = (res_try < res_cols) & active[:, None]
                any_imp = improve.any(axis=1)
                stopped |= active & ~any_imp   # sequential's permanent break
                if b.ndim == 2:     # vector RHS: whole-x accept per system
                    x = np.where(any_imp[:, None], x_try, x)
                else:               # accept only the improving columns
                    x = np.where(improve[:, None, :], x_try, x)
                res_cols = np.where(improve, res_try, res_cols)
                accepted += any_imp
                for i in np.flatnonzero(any_imp):
                    histories[int(i)].append(float(res_cols[i].max()))
    return BatchedSolveResult(x=x, residuals=histories, num=bnum,
                              solve_s=time.perf_counter() - t0,
                              refine_accepted=accepted)
