"""Supernodal left-looking numeric LU on packed CSC-panel storage
(DESIGN.md §4, storage layout §9).

This is the step the symbolic phase exists to feed: ``CSRMatrix`` values plus
a ``SymbolicResult`` (counts, supernodes) in, unit-lower L and upper U out,
factorized panel-by-panel **directly in O(nnz(L+U)) packed storage**
(``storage.PanelStore``) — no dense (n, n) working matrix:

* **Panel gather** — each supernode J = [s, e) owns one contiguous
  (rows_J, w) block; ancestor U rows and L panels are gathered into dense
  operands through the store's sorted row-index maps (absent rows are
  structural zeros), which is what dense hardware wants (GLU3.0-style
  batched updates; structure-aware blocking per arXiv:2512.04389).
* **Left-looking updates** — ancestors K of J (supernodes with a structural
  ``U(K, J)`` block, schedule.py) are consumed in ascending order: solve
  ``U(K, J) = L(K, K)^{-1} X(K, J)``, scatter the rank-|K| update into the
  rows of *later* ancestors, and **defer the whole trailing update to one
  accumulated GEMM** ``X(s:, J) -= L(s:, anc) @ U(anc, J)`` over the gathered
  ancestor columns — the MXU panel-update kernel
  (``kernels/panel_update.py``; numpy float64 BLAS on the default backend)
  reads and writes the packed blocks.
* **Panel factor** — dense no-pivot LU of the diagonal block (raising
  ``ZeroPivotError`` with the global column on zero/near-zero pivots), then
  one triangular solve for the below-panel L rows.
* **Level schedule** — panels are processed by dependency level
  (schedule.py); within a level they are independent and grouped by the
  ``pack_panels`` bins.  The factors are bitwise invariant to the packing
  policy (LPT vs contiguous) because per-panel math never reads same-level
  data.

Structural exactness: updates and solves only ever touch the structural rows
of the predicted pattern, so entries outside the symbolic prediction are
*exactly* zero except at a panel's explicit-zero padding (union rows /
relaxed T3 merges), which is bounded by ``pattern_tol`` and zeroed —
anything larger escaping the pattern raises (that would be a symbolic bug,
the ``validate_symbolic`` contract).  Updates that would land on a row
absent from the target panel's structure are tracked the same way instead
of being silently dropped.

``sparse/numeric.py::lu_nopivot`` stays the dense O(n^2) test oracle
(``NumericResult.l`` / ``.u`` reconstruct dense factors on demand so the
parity tests stay bitwise-meaningful); ``factorize_columns`` is the honest
column-at-a-time sparse baseline the benchmark compares against.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Optional, Tuple

import numpy as np
from scipy.linalg import solve_triangular

from repro.numeric.schedule import PanelSchedule, build_panel_maps, build_schedule
from repro.numeric.storage import BatchedPanelStore, CSCPattern, PanelStore
from repro.obs import metrics as _om
from repro.obs import trace as _ot
from repro.sparse.csr import CSRMatrix
from repro.sparse.numeric import (
    PerturbState, ZeroPivotError, check_pivot, generic_values_csr,
    lu_inplace, lu_inplace_batched, perturb_threshold, pivot_tolerance,
)

_BACKENDS = ("numpy", "kernel")


@dataclasses.dataclass
class NumericResult:
    """Factors + scheduling/perf counters of one supernodal factorization.

    The factors live in packed CSC-panel storage (``store``); ``l``/``u``
    are dense reconstructions materialized on demand for oracle-parity
    tests and small-n consumers — do not touch them at large n.
    """

    n: int
    store: PanelStore
    schedule: PanelSchedule
    backend: str
    elapsed_s: float
    n_updates: int               # ancestor panel updates consumed
    gemm_flops: int              # flops of the accumulated trailing GEMMs
    outside_max: float           # largest |value| found outside the pattern
    perturbed_pivots: int = 0    # tiny pivots bumped by the robust tier
    _dense_lu: Optional[Tuple[np.ndarray, np.ndarray]] = \
        dataclasses.field(default=None, repr=False)

    @property
    def n_supernodes(self) -> int:
        return self.schedule.n_panels

    @property
    def n_levels(self) -> int:
        return self.schedule.n_levels

    @property
    def store_entries(self) -> int:
        """Allocated packed slots — O(nnz(L+U)), the whole point."""
        return self.store.total_entries

    def _dense(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._dense_lu is None:
            self._dense_lu = self.store.dense_lu()
        return self._dense_lu

    @property
    def l(self) -> np.ndarray:
        """Dense unit-lower L — test/oracle reconstruction helper."""
        return self._dense()[0]

    @property
    def u(self) -> np.ndarray:
        """Dense upper U — test/oracle reconstruction helper."""
        return self._dense()[1]

    def reconstruct(self) -> np.ndarray:
        """L @ U — for residual checks against the assembled matrix."""
        return self.l @ self.u


def _solve_unit_lower(block: np.ndarray, rhs: np.ndarray) -> np.ndarray:
    """X with (I + strict_lower(block)) @ X = rhs (block stores L\\U packed)."""
    if block.shape[0] == 1:
        return rhs.copy()
    return solve_triangular(block, rhs, lower=True, unit_diagonal=True,
                            check_finite=False)


def _solve_upper_right(block: np.ndarray, rhs: np.ndarray) -> np.ndarray:
    """X with X @ triu(block) = rhs (below-panel L rows)."""
    if block.shape[0] == 1:
        return rhs / block[0, 0]
    return solve_triangular(block, rhs.T, lower=False, trans="T",
                            check_finite=False).T


def _panel_prepare(store: PanelStore, schedule: PanelSchedule, j: int,
                   maps=None):
    """Phase A of panel j: per-ancestor solves + U-row scatter.

    Runs the ascending per-ancestor unit-lower solves and rank updates on
    the gathered target rows, writes the solved U(anc, J) rows back into
    the packed block, and assembles the trailing-GEMM operands.  Reads only
    strictly-earlier-level blocks, so phase A of every panel in a level can
    run before any same-level GEMM/finish — the batched segment sweep's
    legality contract.

    Returns (lp, b, dropped, flops): the gathered (M, K) ancestor L panel
    and solved (K, w) U rows — the trailing-GEMM operands — plus the
    largest |value| the solves produced on a row absent from the panel's
    structure and the analytic GEMM flop count.  ``(None, None, 0.0, 0)``
    when the panel has no ancestors.
    """
    s, e = schedule.supernodes[j]
    w = e - s
    anc = schedule.ancestors[j]
    block = store.blocks[j]
    d = int(store.diag[j])
    if not len(anc):
        return None, None, 0.0, 0
    if maps is None:
        maps = build_panel_maps(store, schedule, j)
    offs = maps.offs
    anc_rows = maps.anc_rows

    # ascending per-ancestor solves + rank-|K| updates on the gathered
    # target rows; each ancestor's L strip (its own diagonal block + the
    # later ancestor rows) is gathered through the row-index maps only
    # while in use, so working memory stays O(K * max_w) — never a dense
    # (K, K) ancestor sub-matrix (rows absent from a panel's structure
    # gather as exact zeros)
    b = store.gather_rows_mapped(j, maps.idx_j, maps.hit_j)  # (K, w)
    for idx, k in enumerate(anc):
        r0, r1 = offs[idx], offs[idx + 1]
        strip = store.gather_rows_mapped(int(k), *maps.strip_maps[idx])
        b[r0:r1] = _solve_unit_lower(strip[:r1 - r0], b[r0:r1])
        if r1 < len(anc_rows):
            b[r1:] -= strip[r1 - r0:] @ b[r0:r1]
    idx_j, hit_j = maps.idx_j, maps.hit_j         # solved U(anc, J)
    block[idx_j[hit_j]] = b[hit_j]
    dropped = 0.0
    if not hit_j.all():
        miss = np.abs(b[~hit_j])
        if miss.size:
            dropped = float(miss.max())

    # trailing-GEMM operands: the gathered ancestor L panels against the
    # solved U rows, targeting the packed block rows >= s
    below = store.rows[j][d:]
    lp = np.empty((len(below), len(anc_rows)), dtype=np.float64)
    for idx, k in enumerate(anc):
        lp[:, offs[idx]:offs[idx + 1]] = store.gather_rows_mapped(
            int(k), *maps.below_maps[idx])
    flops = 2 * len(below) * len(anc_rows) * w
    return lp, b, dropped, flops


def _panel_finish(store: PanelStore, schedule: PanelSchedule, j: int,
                  piv_tol: float,
                  perturb: PerturbState | None = None) -> None:
    """Phase B of panel j: diagonal-block factor + below-panel solve."""
    s, e = schedule.supernodes[j]
    w = e - s
    block = store.blocks[j]
    d = int(store.diag[j])
    lu_inplace(block[d:d + w], piv_tol, col0=s, perturb=perturb)
    if block.shape[0] > d + w:
        block[d + w:] = _solve_upper_right(block[d:d + w], block[d + w:])


def _factor_panel(store: PanelStore, schedule: PanelSchedule, j: int,
                  piv_tol: float, backend: str,
                  maps=None,
                  perturb: PerturbState | None = None
                  ) -> Tuple[int, int, float]:
    """Factor panel j in place on its packed block (per-panel dispatch).

    ``maps`` (a ``schedule.PanelMaps``) supplies the panel's precomputed
    row-index gather/scatter maps — the plan/factor API builds them once per
    analysis; when omitted they are derived on the fly (one-shot path).  The
    float operations are identical either way, so the factors are bitwise
    the same.

    Returns (#ancestor updates, trailing flops, largest |value| the solves
    produced on a row absent from the panel's structure — nonzero beyond
    roundoff means symbolic under-prediction).
    """
    with _ot.span("panel_prepare"):
        lp, b, dropped, flops = _panel_prepare(store, schedule, j, maps=maps)
    if lp is not None:
        # accumulated trailing update: one GEMM over the gathered ancestor
        # L panels against the solved U rows (MXU kernel on TPU), writing
        # straight back into the packed block rows >= s
        block = store.blocks[j]
        d = int(store.diag[j])
        with _ot.span("panel_gemm"):
            acc = block[d:]
            if backend == "kernel":
                from repro.kernels import ops as kops

                upd = np.asarray(kops.panel_update(acc, lp, b),
                                 dtype=np.float64)
            else:
                upd = acc - lp @ b
            block[d:] = upd
    with _ot.span("panel_finish"):
        _panel_finish(store, schedule, j, piv_tol, perturb=perturb)
    return len(schedule.ancestors[j]), flops, dropped


def _factor_segment_batched(store: PanelStore, schedule: PanelSchedule,
                            seg, piv_tol: float, backend: str, maps=None,
                            perturb: PerturbState | None = None):
    """Factor one (level, device) panel segment with same-shape GEMMs
    stacked into single batched dispatches (DESIGN.md §13).

    Three phases over the whole segment: prepare operands for every panel
    (``_panel_prepare``), apply the trailing GEMMs — panels sharing an
    (M, K, N) operand shape go through ONE stacked dispatch
    (``np.matmul`` on the numpy backend, the vmapped
    ``kernels.ops.panel_update_batched`` Pallas launch on the kernel
    backend) instead of one call each — then run every diagonal factor
    (``_panel_finish``) in segment order.  Panels within a level only read
    strictly-earlier levels and write their own block, so the phase split
    and the shape grouping cannot change a single float op: the batched
    stacks are bitwise-identical to per-panel dispatch (per-slice
    ``np.matmul`` parity on CPU, per-slice grid parity under ``vmap`` on
    the Pallas side).

    Returns per-panel ``(j, n_updates, flops, dropped)`` tuples so the
    caller's accounting matches the per-panel path exactly.
    """
    out = []
    operands = {}
    groups: dict = {}
    with _ot.span("panel_prepare"):
        for j in seg:
            j = int(j)
            lp, b, dropped, flops = _panel_prepare(
                store, schedule, j,
                maps=maps[j] if maps is not None else None)
            out.append((j, len(schedule.ancestors[j]), flops, dropped))
            if lp is None:
                continue
            operands[j] = (lp, b)
            groups.setdefault(lp.shape + (b.shape[1],), []).append(j)

    obs_on = _ot.ENABLED
    batched_calls = 0
    batched_panels = 0
    for (m, k, w), js in groups.items():
        if len(js) == 1:
            # singleton shape: plain per-panel dispatch (identical floats)
            j = js[0]
            lp, b = operands[j]
            block = store.blocks[j]
            d = int(store.diag[j])
            with _ot.span("panel_gemm"):
                acc = block[d:]
                if backend == "kernel":
                    from repro.kernels import ops as kops

                    upd = np.asarray(kops.panel_update(acc, lp, b),
                                     dtype=np.float64)
                else:
                    upd = acc - lp @ b
                block[d:] = upd
            continue
        # stacked same-shape group: one dispatch covers the whole stack,
        # device-resident on the kernel backend (the segment's
        # jax.default_device context owns the transfer + launch)
        with _ot.span("panel_gemm"):
            accs = np.stack([store.blocks[j][int(store.diag[j]):]
                             for j in js])
            lps = np.stack([operands[j][0] for j in js])
            bs = np.stack([operands[j][1] for j in js])
            if backend == "kernel":
                from repro.kernels import ops as kops

                upds = np.asarray(kops.panel_update_batched(accs, lps, bs),
                                  dtype=np.float64)
            else:
                upds = accs - np.matmul(lps, bs)
            for bi, j in enumerate(js):
                d = int(store.diag[j])
                store.blocks[j][d:] = upds[bi]
        batched_calls += 1
        batched_panels += len(js)
        if obs_on:
            reg = _om.registry()
            reg.count("gemm.batched.flops", 2 * len(js) * m * k * w)
            reg.count("gemm.batched.bytes",
                      8 * len(js) * (m * k + k * w + 2 * m * w))
    if obs_on and batched_calls:
        reg = _om.registry()
        reg.count("gemm.batched.calls", batched_calls)
        reg.count("gemm.batched.panels", batched_panels)

    with _ot.span("panel_finish"):
        for j in seg:
            _panel_finish(store, schedule, int(j), piv_tol, perturb=perturb)
    return out


def factor_on_store(a: Optional[CSRMatrix], values: np.ndarray,
                    store: PanelStore, schedule: PanelSchedule, *,
                    backend: str = "numpy",
                    piv_tol: Optional[float] = None,
                    check_pattern: bool = True,
                    pattern_tol: Optional[float] = None,
                    maps=None, csr_maps=None,
                    store_is_zeroed: bool = False,
                    placement=None,
                    segment_batch: bool = True,
                    perturb: bool = False,
                    perturb_eps: Optional[float] = None) -> NumericResult:
    """Scatter ``values`` into ``store`` and run the level-scheduled panel
    sweep — the value-dependent core shared by one-shot
    ``numeric_factorize`` and plan-based ``LUPlan.factorize`` (which passes
    precomputed ``maps``/``csr_maps`` so nothing value-independent is
    rebuilt).  Both paths execute identical float operations, so the
    factors are bitwise-identical by construction.

    ``placement`` (a ``schedule.PanelPlacement``) splits every level into
    per-device panel segments (DESIGN.md §11): segments are the dispatch
    unit — on the "kernel" backend each segment's accumulated GEMMs are
    issued under its device's ``jax.default_device`` so XLA overlaps the
    per-device streams; on the "numpy" backend segments order the sweep.
    Panels within a level only ever read strictly-earlier levels and write
    their own block, so segment grouping cannot change a single float op:
    factors stay bitwise-identical at every device count.

    ``segment_batch`` (default on) routes each segment through
    ``_factor_segment_batched``: same-shape panels issue ONE stacked GEMM
    dispatch instead of one per panel — bitwise-identical floats, far
    fewer kernel launches (DESIGN.md §13).  Off = legacy per-panel
    dispatch, kept as the benchmark comparison point.

    ``perturb`` enables tiny-pivot perturbation (DESIGN.md §15): pivots
    with |piv| <= ``perturb_eps``·max|A| (default sqrt(machine eps)) are
    replaced by the signed threshold instead of raising; the count lands in
    ``NumericResult.perturbed_pivots`` and iterative refinement downstream
    recovers the accuracy.  Off (default), the float operations are the
    historical ones bit for bit."""
    if backend not in _BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; pick from {_BACKENDS}")
    n = store.n
    if pattern_tol is None:
        # float32 MXU updates leave f32-roundoff garbage at the explicit
        # zeros of relaxed panels; the float64 path stays at f64 roundoff
        pattern_tol = 1e-4 if backend == "kernel" else 1e-8
    t0 = time.perf_counter()

    values = np.asarray(values, dtype=np.float64)
    with _ot.span("scatter_values"):
        if values.ndim == 2:
            if values.shape != (n, n):
                raise ValueError(
                    f"values must be ({n}, {n}), got {values.shape}")
            input_outside = store.set_dense(values)
        else:
            if csr_maps is None and a is None:
                raise ValueError(
                    "CSR-aligned values need the matrix `a` or precomputed "
                    "`csr_maps` to locate their slots")
            nnz = csr_maps.nnz if csr_maps is not None else a.nnz
            if values.shape != (nnz,):
                raise ValueError(
                    f"values must be dense ({n}, {n}) or CSR-aligned "
                    f"({nnz},), got {values.shape}")
            input_outside = (
                store.set_csr_mapped(values, csr_maps,
                                     zero=not store_is_zeroed)
                if csr_maps is not None else store.set_csr(a, values))

    scale = float(np.abs(values).max()) if values.size else 0.0
    if piv_tol is None:
        piv_tol = pivot_tolerance(scale)
    pstate = PerturbState(perturb_threshold(scale, perturb_eps)) \
        if perturb else None

    # per-device dispatch contexts: only the jax kernel backend has device
    # placement to exploit; numpy BLAS segments are a pure scheduling order
    devices = None
    if (placement is not None and placement.n_devices > 1
            and backend == "kernel"):
        import jax

        if len(jax.devices()) < placement.n_devices:
            raise ValueError(
                f"placement spans {placement.n_devices} devices but only "
                f"{len(jax.devices())} are visible; re-place the plan "
                f"(plan.place()) for this host")
        devices = jax.devices()[:placement.n_devices]

    n_updates = 0
    gemm_flops = 0
    dropped_max = input_outside
    # obs accounting (only touched when tracing is enabled): analytic GEMM
    # traffic accumulates from shapes the sweep already knows — never a
    # per-panel timer, so the disabled path and the ratio gates see zero cost
    obs_on = _ot.ENABLED
    gemm_bytes = 0
    sweep_t0 = time.perf_counter() if obs_on else 0.0
    for li, level in enumerate(schedule.levels):
        if placement is None or placement.n_devices <= 1:
            segments = ((None, level),)
        else:
            segments = tuple(
                (d, seg) for d, seg in enumerate(placement.segments(level))
                if len(seg))
        seg_times = [] if obs_on and len(segments) > 1 else None
        with _ot.span("factor_level"):
            for d, seg in segments:
                ctx = (jax.default_device(devices[d])
                       if devices is not None and d is not None
                       else contextlib.nullcontext())
                track = f"device {d}" if d is not None else None
                seg_t0 = time.perf_counter() if seg_times is not None else 0.0
                with ctx, _ot.span("factor_segment", track=track):
                    try:
                        if segment_batch and len(seg) > 1:
                            panel_stats = _factor_segment_batched(
                                store, schedule, seg, piv_tol, backend,
                                maps=maps, perturb=pstate)
                        else:
                            panel_stats = [
                                (int(j),) + _factor_panel(
                                    store, schedule, int(j), piv_tol, backend,
                                    maps=maps[j] if maps is not None else None,
                                    perturb=pstate)
                                for j in seg]
                    except ZeroPivotError as e:
                        raise e.with_context(
                            panel=int(store.sup_of_col[e.k]), level=li)
                    for j, upd, flops, dropped in panel_stats:
                        n_updates += upd
                        gemm_flops += flops
                        dropped_max = max(dropped_max, dropped)
                        if obs_on and flops:
                            s_, e_ = schedule.supernodes[j]
                            w_ = int(e_ - s_)
                            nb = (len(store.rows[j]) - int(store.diag[j]))
                            k_ = flops // (2 * nb * w_)
                            # gathered L panel + solved U rows read, target
                            # block read + written, all float64
                            gemm_bytes += 8 * (nb * k_ + k_ * w_ + 2 * nb * w_)
                if seg_times is not None:
                    seg_times.append(time.perf_counter() - seg_t0)
        if seg_times is not None and len(seg_times) > 1:
            mean_t = sum(seg_times) / len(seg_times)
            if mean_t > 0:
                _om.registry().observe("factor.level_imbalance_measured",
                                       max(seg_times) / mean_t)
    if obs_on:
        reg = _om.registry()
        reg.count("gemm.flops", gemm_flops)
        reg.count("gemm.bytes", gemm_bytes)
        reg.count("gemm.seconds", time.perf_counter() - sweep_t0)
        if pstate is not None and pstate.count:
            reg.count("robust.perturbed_pivots", int(pstate.count))

    outside_max = max(store.padding_max(), dropped_max)
    if check_pattern and outside_max > pattern_tol * scale:
        raise ValueError(
            f"numeric factorization escaped the symbolic prediction: "
            f"|{outside_max:.3e}| outside the pattern (tol "
            f"{pattern_tol * scale:.3e}) — symbolic under-prediction")
    store.zero_padding()

    return NumericResult(n=n, store=store, schedule=schedule, backend=backend,
                         elapsed_s=time.perf_counter() - t0,
                         n_updates=n_updates, gemm_flops=gemm_flops,
                         outside_max=outside_max,
                         perturbed_pivots=(pstate.count if pstate else 0))


@dataclasses.dataclass
class BatchedNumericResult:
    """Factors of B same-pattern value sets in one ``BatchedPanelStore``
    (DESIGN.md §14).

    ``n_updates``/``gemm_flops`` are *per system* — the sweep structure is
    value-independent, so every system does identical work and the numbers
    match what a standalone ``factor_on_store`` of any one system reports.
    ``outside_max`` is the (B,) per-system escape check.  ``system(i)``
    wraps system i's zero-copy store view as a plain ``NumericResult`` so
    per-system consumers (solve, dense oracle reconstruction, parity
    tests) run unchanged on batched factors.
    """

    n: int
    batch: int
    store: BatchedPanelStore
    schedule: PanelSchedule
    backend: str
    elapsed_s: float
    n_updates: int               # ancestor panel updates, per system
    gemm_flops: int              # trailing-GEMM flops, per system
    outside_max: np.ndarray      # (B,) largest |value| outside the pattern
    perturbed_pivots: Optional[np.ndarray] = None   # (B,) per-system counts

    @property
    def n_supernodes(self) -> int:
        return self.schedule.n_panels

    @property
    def n_levels(self) -> int:
        return self.schedule.n_levels

    def system(self, i: int) -> NumericResult:
        return NumericResult(n=self.n, store=self.store.system(i),
                             schedule=self.schedule, backend=self.backend,
                             elapsed_s=0.0, n_updates=self.n_updates,
                             gemm_flops=self.gemm_flops,
                             outside_max=float(self.outside_max[i]),
                             perturbed_pivots=(
                                 int(self.perturbed_pivots[i])
                                 if self.perturbed_pivots is not None else 0))


def _panel_prepare_batched(bstore: BatchedPanelStore,
                           schedule: PanelSchedule, j: int, maps=None):
    """``_panel_prepare`` broadcast over the system axis of a
    ``BatchedPanelStore``: one gather / rank-update pass serves all B
    systems.  Gathers and rank updates are batched (fancy indexing and
    stacked ``np.matmul`` are per-slice bitwise-identical to their 2D
    forms); the per-ancestor unit-lower solves stay per-system LAPACK
    calls, so every float op matches ``_panel_prepare`` on that system
    alone — the batched tier's conformance contract (DESIGN.md §14).

    Returns (lp (B, M, K), b (B, K, w), dropped (B,), flops-per-system).
    """
    s, e = schedule.supernodes[j]
    w = e - s
    anc = schedule.ancestors[j]
    block = bstore.blocks[j]
    d = int(bstore.diag[j])
    bsz = bstore.batch
    if not len(anc):
        return None, None, np.zeros(bsz, dtype=np.float64), 0
    if maps is None:
        maps = build_panel_maps(bstore.template, schedule, j)
    offs = maps.offs
    anc_rows = maps.anc_rows

    b = bstore.gather_rows_mapped(j, maps.idx_j, maps.hit_j)  # (B, K, w)
    for idx, k in enumerate(anc):
        r0, r1 = offs[idx], offs[idx + 1]
        strip = bstore.gather_rows_mapped(int(k), *maps.strip_maps[idx])
        if r1 - r0 > 1:           # 1-row solves are identity (unit lower)
            head = strip[:, :r1 - r0]
            for i in range(bsz):
                b[i, r0:r1] = solve_triangular(head[i], b[i, r0:r1],
                                               lower=True,
                                               unit_diagonal=True,
                                               check_finite=False)
        if r1 < len(anc_rows):
            b[:, r1:] -= np.matmul(strip[:, r1 - r0:], b[:, r0:r1])
    idx_j, hit_j = maps.idx_j, maps.hit_j         # solved U(anc, J)
    block[:, idx_j[hit_j]] = b[:, hit_j]
    dropped = np.zeros(bsz, dtype=np.float64)
    if not hit_j.all():
        miss = b[:, ~hit_j]
        if miss.size:
            dropped = np.abs(miss.reshape(bsz, -1)).max(axis=1)

    below = bstore.rows[j][d:]
    lp = np.empty((bsz, len(below), len(anc_rows)), dtype=np.float64)
    for idx, k in enumerate(anc):
        lp[:, :, offs[idx]:offs[idx + 1]] = bstore.gather_rows_mapped(
            int(k), *maps.below_maps[idx])
    flops = 2 * len(below) * len(anc_rows) * w
    return lp, b, dropped, flops


def _panel_finish_batched(bstore: BatchedPanelStore,
                          schedule: PanelSchedule, j: int,
                          piv_tol: np.ndarray,
                          perturb: PerturbState | None = None) -> None:
    """``_panel_finish`` over the system axis: elementwise batched
    diagonal LU (``lu_inplace_batched``) + per-system LAPACK below-panel
    solves; ``piv_tol`` is the (B,) per-system threshold."""
    s, e = schedule.supernodes[j]
    w = e - s
    block = bstore.blocks[j]
    d = int(bstore.diag[j])
    lu_inplace_batched(block[:, d:d + w], piv_tol, col0=s, perturb=perturb)
    if block.shape[1] > d + w:
        diag = block[:, d:d + w]
        for i in range(bstore.batch):
            block[i, d + w:] = _solve_upper_right(diag[i], block[i, d + w:])


def factor_batch_on_store(a: Optional[CSRMatrix], values_batch: np.ndarray,
                          bstore: BatchedPanelStore,
                          schedule: PanelSchedule, *,
                          backend: str = "numpy",
                          piv_tol: Optional[float] = None,
                          check_pattern: bool = True,
                          pattern_tol: Optional[float] = None,
                          maps=None, csr_maps=None,
                          store_is_zeroed: bool = False,
                          perturb: bool = False,
                          perturb_eps: Optional[float] = None
                          ) -> BatchedNumericResult:
    """``factor_on_store`` vmapped over B same-pattern value sets
    (DESIGN.md §14): scatter the (B, nnz) CSR-aligned stack into the
    batched store and run ONE level-scheduled sweep whose every per-panel
    operation carries a leading system axis.

    System i's factors are **bitwise-identical** to
    ``factor_on_store(a, values_batch[i], ...)`` on a standalone store:
    gathers/scatters and the trailing GEMMs broadcast over the batch
    (per-slice ``np.matmul`` parity on CPU, per-slice grid parity of the
    stacked Pallas dispatch on the kernel backend), the diagonal LU is the
    elementwise ``lu_inplace_batched``, and the triangular solves stay
    per-system LAPACK calls.  Pivot tolerance, the pattern-escape check,
    and ``ZeroPivotError`` are all per system (``piv_tol=None`` derives
    each system's threshold from its own value scale).

    Same-shape panels of a level additionally stack across the batch into
    one (panels x B)-deep GEMM dispatch — the within-plan segment batching
    of DESIGN.md §13 composed with the system axis.  Only CSR-aligned
    (B, nnz) values are supported (the batch tier is the refactorization
    server path; dense (n, n) stacks would defeat its memory point).
    """
    if backend not in _BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; pick from {_BACKENDS}")
    n = bstore.n
    bsz = bstore.batch
    if pattern_tol is None:
        pattern_tol = 1e-4 if backend == "kernel" else 1e-8
    t0 = time.perf_counter()

    values_batch = np.asarray(values_batch, dtype=np.float64)
    if csr_maps is None:
        if a is None:
            raise ValueError(
                "batched CSR values need the matrix `a` or precomputed "
                "`csr_maps` to locate their slots")
        csr_maps = bstore.template.csr_maps(a)
    if values_batch.shape != (bsz, csr_maps.nnz):
        raise ValueError(
            f"values_batch must be ({bsz}, {csr_maps.nnz}) CSR-aligned, "
            f"got {values_batch.shape}")
    with _ot.span("scatter_values"):
        input_outside = bstore.set_csr_mapped(values_batch, csr_maps,
                                              zero=not store_is_zeroed)

    scale = (np.abs(values_batch).max(axis=1) if values_batch.size
             else np.zeros(bsz, dtype=np.float64))
    if piv_tol is None:
        # vectorized pivot_tolerance: eps at each system's own value scale
        piv_tol_sys = np.finfo(np.float64).eps * np.maximum(scale, 0.0)
    else:
        piv_tol_sys = np.full(bsz, float(piv_tol))
    eps = np.float64(perturb_threshold(1.0, perturb_eps))
    pstate = PerturbState(eps * np.maximum(scale, 0.0)) if perturb else None

    n_updates = 0
    gemm_flops = 0
    dropped_max = input_outside.copy()
    obs_on = _ot.ENABLED
    sweep_t0 = time.perf_counter() if obs_on else 0.0
    batched_calls = 0
    batched_panels = 0
    for li, level in enumerate(schedule.levels):
        with _ot.span("factor_level"):
            operands = {}
            groups: dict = {}
            with _ot.span("panel_prepare"):
                for j in level:
                    j = int(j)
                    lp, b, dropped, flops = _panel_prepare_batched(
                        bstore, schedule, j,
                        maps=maps[j] if maps is not None else None)
                    n_updates += len(schedule.ancestors[j])
                    gemm_flops += flops
                    np.maximum(dropped_max, dropped, out=dropped_max)
                    if lp is None:
                        continue
                    operands[j] = (lp, b)
                    groups.setdefault(lp.shape[1:] + (b.shape[2],),
                                      []).append(j)

            for (m, k, w), js in groups.items():
                if len(js) == 1:
                    # one panel, B systems: the (B, ., .) stack IS the batch
                    j = js[0]
                    lp, b = operands[j]
                    d = int(bstore.diag[j])
                    with _ot.span("panel_gemm"):
                        acc = bstore.blocks[j][:, d:]
                        if backend == "kernel":
                            from repro.kernels import ops as kops

                            upd = np.asarray(
                                kops.panel_update_systems(acc, lp, b),
                                dtype=np.float64)
                        else:
                            upd = acc - np.matmul(lp, b)
                        bstore.blocks[j][:, d:] = upd
                    continue
                # same-shape panel group x system batch: one stacked dispatch
                with _ot.span("panel_gemm"):
                    accs = np.concatenate(
                        [bstore.blocks[j][:, int(bstore.diag[j]):]
                         for j in js])
                    lps = np.concatenate([operands[j][0] for j in js])
                    bs = np.concatenate([operands[j][1] for j in js])
                    if backend == "kernel":
                        from repro.kernels import ops as kops

                        upds = np.asarray(
                            kops.panel_update_systems(accs, lps, bs),
                            dtype=np.float64)
                    else:
                        upds = accs - np.matmul(lps, bs)
                    for gi, j in enumerate(js):
                        d = int(bstore.diag[j])
                        bstore.blocks[j][:, d:] = upds[gi * bsz:
                                                       (gi + 1) * bsz]
                batched_calls += 1
                batched_panels += len(js)
                if obs_on:
                    reg = _om.registry()
                    reg.count("gemm.batched.flops",
                              2 * len(js) * bsz * m * k * w)
                    reg.count("gemm.batched.bytes",
                              8 * len(js) * bsz * (m * k + k * w + 2 * m * w))

            with _ot.span("panel_finish"):
                for j in level:
                    try:
                        _panel_finish_batched(bstore, schedule, int(j),
                                              piv_tol_sys, perturb=pstate)
                    except ZeroPivotError as e:
                        raise e.with_context(panel=int(j), level=li)
    if obs_on:
        reg = _om.registry()
        if batched_calls:
            reg.count("gemm.batched.calls", batched_calls)
            reg.count("gemm.batched.panels", batched_panels)
        reg.count("gemm.flops", gemm_flops * bsz)
        reg.count("gemm.seconds", time.perf_counter() - sweep_t0)
        if pstate is not None and pstate.total():
            reg.count("robust.perturbed_pivots", pstate.total())

    outside_max = np.maximum(bstore.padding_max(), dropped_max)
    bad = outside_max > pattern_tol * scale
    if check_pattern and bad.any():
        i = int(np.flatnonzero(bad)[0])
        raise ValueError(
            f"numeric factorization escaped the symbolic prediction: "
            f"system {i} has |{outside_max[i]:.3e}| outside the pattern "
            f"(tol {pattern_tol * scale[i]:.3e}) — symbolic "
            f"under-prediction")
    bstore.zero_padding()

    return BatchedNumericResult(n=n, batch=bsz, store=bstore,
                                schedule=schedule, backend=backend,
                                elapsed_s=time.perf_counter() - t0,
                                n_updates=n_updates, gemm_flops=gemm_flops,
                                outside_max=outside_max,
                                perturbed_pivots=(
                                    pstate.count if pstate is not None
                                    else np.zeros(bsz, dtype=np.int64)))


def numeric_factorize(a: CSRMatrix, sym=None, *,
                      values: Optional[np.ndarray] = None,
                      pattern=None,
                      supernodes: Optional[np.ndarray] = None,
                      n_bins: int = 8, policy: str = "lpt",
                      backend: str = "numpy",
                      piv_tol: Optional[float] = None,
                      check_pattern: bool = True,
                      pattern_tol: Optional[float] = None,
                      segment_batch: bool = True) -> NumericResult:
    """Supernodal left-looking LU of ``values`` on A's structure, factored
    in O(nnz(L+U)) packed CSC-panel storage.

    ``a``: structural CSR; ``sym``: a ``SymbolicResult`` from
    ``symbolic_factorize(a, detect_supernodes=True)`` (computed on the fly
    when omitted; without a supernode partition the serial detector runs on
    the pattern).  ``supernodes``: explicit (k, 2) panel ranges, overriding
    ``sym`` — any contiguous partition is valid (padding absorbs
    non-uniform structure exactly like relaxed T3 merges).

    ``values``: either dense (n, n) float64 on A's pattern (legacy
    oracle-friendly form) or CSR-aligned (nnz,) float64 pairing
    ``a.indices`` — the sparse form never materializes (n, n) and is the
    one to use at large n (defaults to ``generic_values_csr(a)``).
    ``pattern``: the predicted L+U pattern as dense (n, n) bool or a
    ``storage.CSCPattern`` (recomputed from the graph when omitted — a
    dense small-n convenience).  ``backend``: "numpy" (float64 BLAS,
    default) or "kernel" (float32 Pallas MXU panel updates — TPU precision
    documented in DESIGN.md §4).

    Raises ``ZeroPivotError`` (global column index) on zero/near-zero pivots
    and ``ValueError`` if any value above ``pattern_tol * scale`` escapes the
    symbolic prediction (the ``validate_symbolic`` contract).

    This rebuilds the schedule, the packed store structure, and the gather
    maps from scratch on *every* call; refactorization workloads (same
    pattern, new values) should use ``repro.analyze`` once and
    ``LUPlan.factorize`` per value set instead (repro.api, DESIGN.md §10).
    """
    if backend not in _BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; pick from {_BACKENDS}")
    t0 = time.perf_counter()
    n = a.n

    if values is None:
        values = generic_values_csr(a)
    values = np.asarray(values, dtype=np.float64)
    if values.ndim == 2:
        if values.shape != (n, n):
            raise ValueError(f"values must be ({n}, {n}), got {values.shape}")
    elif values.shape != (a.nnz,):
        raise ValueError(
            f"values must be dense ({n}, {n}) or CSR-aligned ({a.nnz},), "
            f"got {values.shape}")

    if pattern is None:
        from repro.core.gsofa import dense_pattern, prepare_graph

        pattern = dense_pattern(prepare_graph(a))
    if not isinstance(pattern, CSCPattern):
        pattern = np.asarray(pattern, dtype=bool)
        if pattern.shape != (n, n):
            raise ValueError(f"pattern must be ({n}, {n}), got "
                             f"{pattern.shape}")
        pattern = CSCPattern.from_dense(pattern)
    else:
        pattern = pattern.with_diagonal()
    if pattern.n != n:
        raise ValueError(f"pattern is for n={pattern.n}, matrix has n={n}")

    if supernodes is None:
        if sym is None:
            from repro.core.symbolic import symbolic_factorize

            sym = symbolic_factorize(a, detect_supernodes=True)
        if sym.n != n:
            raise ValueError(
                f"symbolic result is for n={sym.n}, matrix has n={n}")
        supernodes = sym.supernodes
        if supernodes is None:
            from repro.core.symbolic import detect_supernodes as _detect

            supernodes = _detect(pattern.to_dense())

    schedule = build_schedule(pattern, supernodes, n_bins=n_bins,
                              policy=policy)
    store = PanelStore(pattern, schedule.supernodes)
    result = factor_on_store(a, values, store, schedule, backend=backend,
                             piv_tol=piv_tol, check_pattern=check_pattern,
                             pattern_tol=pattern_tol,
                             segment_batch=segment_batch)
    result.elapsed_s = time.perf_counter() - t0
    return result


def factorize_columns(values: np.ndarray, pattern: np.ndarray, *,
                      piv_tol: Optional[float] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Column-at-a-time left-looking sparse LU — the pre-supernodal baseline
    (one axpy per structural U entry, no panel batching), used by
    ``benchmarks/bench_numeric.py`` as the comparison point and by tests as
    an independent implementation.  Same pivot contract as the supernodal
    path."""
    values = np.asarray(values, dtype=np.float64)
    n = values.shape[0]
    pattern = np.asarray(pattern, dtype=bool).copy()
    np.fill_diagonal(pattern, True)
    m = values.copy()
    if piv_tol is None:
        piv_tol = pivot_tolerance(np.abs(m).max() if m.size else 0.0)
    # CSC-style below-diagonal structure of every L column, precomputed
    lrows = [j + 1 + np.flatnonzero(pattern[j + 1:, j]) for j in range(n)]
    for j in range(n):
        for k in np.flatnonzero(pattern[:j, j]):
            rows = lrows[k]
            m[rows, j] -= m[rows, k] * m[k, j]
        piv = m[j, j]
        check_pivot(j, piv, piv_tol)
        m[lrows[j], j] /= piv
    m[~pattern] = 0.0
    l = np.tril(m, -1) + np.eye(n)
    u = np.triu(m)
    return l, u
