"""Plan/factor session API: analyze once, refactorize many, solve multi-RHS.

GSoFa's premise is that symbolic analysis is a separable, reusable phase.
The dominant sparse-LU workload in practice — circuit simulation per GLU3.0
(arXiv:1908.00204) and HYLU (arXiv:2509.07690) — factorizes the *same*
sparsity pattern hundreds of times with new values, so the public API is
built around that split (DESIGN.md §10)::

    import repro

    plan = repro.analyze(a, repro.LUOptions(supernode_relax=2))
    for values in value_stream:            # same pattern, new values
        factor = plan.factorize(values)    # numeric sweep only
        result = factor.solve(b)           # b is (n,) or multi-RHS (n, k)

``analyze`` runs the symbolic fixpoint + streamed supernode detection and
precomputes **everything value-independent**:

* the sparse ``CSCPattern`` of L+U, streamed straight from the fixpoint
  chunks (``core.symbolic.PatternCollector``) — no dense (n, n) pattern is
  ever materialized, at any n;
* the supernode panel partition and ``pack_panels`` bins;
* the factorization level schedule (panel elimination DAG);
* the per-panel sorted-row gather/scatter maps of every ancestor update
  (``schedule.build_gather_maps``) and the CSR value-scatter maps
  (``PanelStore.csr_maps``);
* the forward/backward solve-level DAGs (``build_solve_schedule``);
* a ``PanelStore`` structure template sized from the symbolic prediction.

``LUPlan.factorize(values)`` then runs only the value-dependent panel sweep
(scatter + level-scheduled GEMM updates) on a fresh set of block buffers
sharing the template's structure; ``LUFactorization.refactorize(values)``
goes one step further and reuses the same buffers in place.  Factors are
bitwise-identical to one-shot ``numeric_factorize`` by construction (shared
``factor_on_store`` engine).  Plans hold only numpy arrays and plain
dataclasses, so they pickle — analyses can be cached across processes.

Analysis and factorization distribute (DESIGN.md §11): pass a device mesh
(``launch.mesh.make_flat_mesh``) — or set ``LUOptions(distribute=True)``
to take every visible device — and the symbolic fixpoint shards its
sources over the mesh inside shard_map while the plan gains a
``PanelPlacement`` that splits every dependency level's panels into
per-device segments for factorize and solve.  Factors, solutions, panel
partitions, and patterns are **bitwise-identical at every device count**
(the `tests/test_distributed_plan.py` conformance tier runs {1, 2, 8}
forced host devices), and distributed plans still pickle.

The legacy one-shot trio (``repro.symbolic_factorize`` ->
``repro.numeric_factorize`` -> ``repro.solve``) was removed in 1.4.0
after its announced one-release ``DeprecationWarning`` period; the
engines remain importable from ``repro.core.symbolic`` and
``repro.numeric``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import numpy as np

from repro.core.symbolic import SymbolicResult
from repro.core.symbolic import symbolic_factorize as _symbolic_factorize
from repro.numeric.schedule import (
    PanelPlacement, PanelSchedule, build_gather_maps, build_placement,
    build_schedule,
)
from repro.numeric.solve import (
    BatchedSolveResult, SolveResult, SolveSchedule, build_solve_schedule,
)
from repro.numeric.solve import solve as _solve
from repro.numeric.solve import solve_batch as _solve_batch
from repro.numeric.storage import (
    BatchedPanelStore, CSCPattern, CsrScatterMaps, PanelStore,
)
from repro.numeric.supernodal import (
    BatchedNumericResult, NumericResult, factor_batch_on_store,
    factor_on_store,
)
from repro.obs import trace as _ot
from repro.obs.trace import SpanSummary
from repro.sparse.csr import CSRMatrix
from repro.sparse.numeric import generic_values_csr

_SYMBOLIC_BACKENDS = ("ell", "dense", "kernel")
_NUMERIC_BACKENDS = ("numpy", "kernel")
_POLICIES = ("lpt", "contiguous")
_RUNTIMES = ("static", "dynamic")
_PIVOTS = ("none", "static")


@dataclasses.dataclass(frozen=True)
class LUOptions:
    """Every knob of the symbolic -> numeric -> solve pipeline in one frozen
    object — replaces the kwarg sprawl the three-layer API used to thread.

    Symbolic fixpoint: ``concurrency`` (#C source chunk size), ``backend``
    (relaxation backend), ``combined`` (one batched fixpoint per chunk),
    ``bubble`` (label-window truncation), ``use_arena`` (label re-init
    elision), ``budget_bytes`` (memory envelope -> effective #C),
    ``checkpoint_path`` (per-chunk durable progress).

    Supernodes: ``supernode_relax`` (T3 merge tolerance, 0 = exact T2),
    ``supernode_max_size`` (panel width cap).

    Blocking / autotune (DESIGN.md §16): ``blocking=True`` runs the
    structure-aware irregular merge pass after detection — adjacent
    supernodes with nearly-overlapping row structures coalesce into one
    padded dense block when the roofline cost model says the flop/byte
    gain pays for the explicit zeros (``block_merge_threshold``, default
    1.0 = accept exactly the modeled wins; ``block_max_width`` caps the
    merged panel).  ``autotune=True`` goes further and sweeps
    ``supernode_relax``/``supernode_max_size`` candidates (re-detected
    from the retained fingerprints, no fixpoint re-run) through that
    merge pass, freezing the winning knobs — including a
    ``concurrency`` sized to the label-matrix byte budget — onto the
    plan's options (``LUPlan.tuned`` records the sweep).  Both off by
    default: the defaults are bitwise-identical to the unblocked
    pipeline; blocked partitions regroup float ops and carry
    dense-oracle parity instead.

    Numeric: ``n_bins``/``policy`` (pack_panels within-level grouping),
    ``numeric_backend`` ("numpy" float64 BLAS or "kernel" Pallas MXU),
    ``piv_tol`` (zero-pivot threshold; None = eps at matrix scale),
    ``check_pattern``/``pattern_tol`` (validate_symbolic contract).

    Solve: ``refine_iters``/``refine_tol`` (iterative refinement bounds).

    Robustness (DESIGN.md §15): ``pivot="static"`` adds the analyze-time
    maximum-product transversal + equilibration pre-pass (the factored
    system becomes ``Dr·P·A·Dc``, stored on the plan so refactorization
    stays value-only); ``perturb=True`` replaces tiny pivots
    (|piv| <= ``perturb_eps``·max|A|, default sqrt(machine eps)) with the
    signed threshold during the sweep instead of raising, counting them in
    ``NumericResult.perturbed_pivots`` — iterative refinement recovers the
    accuracy.  Both off by default: the defaults are bitwise-identical to
    the historical pipeline.

    Distribution: ``distribute=True`` makes ``analyze`` build a flat mesh
    over every visible device (``launch.mesh.make_flat_mesh``) when no
    explicit mesh is passed — the symbolic fixpoint shards its sources and
    the plan's panel placement splits level work per device (DESIGN.md
    §11); results are bitwise-identical at any device count.
    """

    # -- symbolic fixpoint
    concurrency: int = 128
    backend: str = "ell"
    combined: bool = True
    bubble: bool = False
    use_arena: bool = True
    budget_bytes: Optional[int] = None
    checkpoint_path: Optional[str] = None
    # -- supernode detection
    supernode_relax: int = 0
    supernode_max_size: int = 64
    # -- structure-aware blocking + roofline autotune (DESIGN.md §16);
    # both off by default (bitwise-identical to the unblocked pipeline)
    blocking: bool = False
    block_merge_threshold: Optional[float] = None   # None = 1.0 (model wins)
    block_max_width: int = 256
    autotune: bool = False
    # -- numeric factorization
    n_bins: int = 8
    policy: str = "lpt"
    numeric_backend: str = "numpy"
    piv_tol: Optional[float] = None
    check_pattern: bool = True
    pattern_tol: Optional[float] = None
    # batch same-shape panels of a (level, device) segment into one stacked
    # GEMM dispatch (DESIGN.md §13) — bitwise-identical to per-panel
    # dispatch; off restores the one-GEMM-per-panel sweep
    segment_batch: bool = True
    # -- solve / refinement
    refine_iters: int = 2
    refine_tol: Optional[float] = None
    # -- numerical robustness (DESIGN.md §15): static pivoting pre-pass at
    # analyze time + tiny-pivot perturbation during the sweep; both off by
    # default (bitwise-identical to the historical path)
    pivot: str = "none"
    perturb: bool = False
    perturb_eps: Optional[float] = None
    # -- distribution (DESIGN.md §11)
    distribute: bool = False
    # -- execution runtime (DESIGN.md §13): "static" = fixed chunk loop;
    # "dynamic" = work-stealing DynamicScheduler over the visible devices
    # (straggler re-issue, elastic join/leave), bitwise-identical outputs
    runtime: str = "static"
    # -- observability (DESIGN.md §12): record phase spans + counters for
    # this plan's analyze/factorize calls (repro.obs); plans/factors gain a
    # ``stats`` summary tree.  Off by default — the disabled path is a
    # module-level boolean check, so it cannot perturb timings.  A running
    # jax.profiler session gets the spans without it (DESIGN.md §12.1).
    trace: bool = False

    def __post_init__(self):
        # Range-check the numeric knobs up front with actionable messages —
        # a bad value would otherwise surface deep inside the fixpoint
        # chunking or panel packing as an opaque shape/index error.
        if self.concurrency < 1:
            raise ValueError(
                f"concurrency must be >= 1 (source-chunk width of the "
                f"symbolic fixpoint), got {self.concurrency}")
        if self.supernode_max_size < 1:
            raise ValueError(
                f"supernode_max_size must be >= 1 (panel width cap; 1 "
                f"disables supernode fusion), got {self.supernode_max_size}")
        if self.supernode_relax < 0:
            raise ValueError(
                f"supernode_relax must be >= 0 (T3 merge tolerance; 0 is "
                f"exact T2), got {self.supernode_relax}")
        if self.n_bins < 1:
            raise ValueError(
                f"n_bins must be >= 1 (pack_panels bins per level), "
                f"got {self.n_bins}")
        if self.refine_iters < 0:
            raise ValueError(
                f"refine_iters must be >= 0 (0 disables iterative "
                f"refinement), got {self.refine_iters}")
        if self.budget_bytes is not None and self.budget_bytes < 1:
            raise ValueError(
                f"budget_bytes must be >= 1 when set (memory envelope for "
                f"the fixpoint working set), got {self.budget_bytes}")
        if self.block_max_width < 1:
            raise ValueError(
                f"block_max_width must be >= 1 (merged-panel column cap "
                f"for blocking/autotune), got {self.block_max_width}")
        if (self.block_merge_threshold is not None
                and not self.block_merge_threshold > 0.0):
            raise ValueError(
                f"block_merge_threshold must be > 0 when set (1.0 accepts "
                f"exactly the modeled wins; larger merges more "
                f"aggressively), got {self.block_merge_threshold!r}")
        if self.backend not in _SYMBOLIC_BACKENDS:
            raise ValueError(f"unknown symbolic backend {self.backend!r}; "
                             f"pick from {_SYMBOLIC_BACKENDS}")
        if self.numeric_backend not in _NUMERIC_BACKENDS:
            raise ValueError(f"unknown numeric backend "
                             f"{self.numeric_backend!r}; pick from "
                             f"{_NUMERIC_BACKENDS}")
        if self.policy not in _POLICIES:
            raise ValueError(f"unknown packing policy {self.policy!r}; "
                             f"pick from {_POLICIES}")
        if self.runtime not in _RUNTIMES:
            raise ValueError(f"unknown runtime {self.runtime!r}; "
                             f"pick from {_RUNTIMES}")
        if self.pivot not in _PIVOTS:
            raise ValueError(f"unknown pivot mode {self.pivot!r}; "
                             f"pick from {_PIVOTS}")
        if self.perturb_eps is not None and not self.perturb_eps > 0.0:
            raise ValueError(f"perturb_eps must be positive, got "
                             f"{self.perturb_eps!r}")
        if self.runtime == "dynamic" and self.distribute:
            raise ValueError(
                "runtime='dynamic' is the host-driven scheduler over the "
                "visible devices and cannot be combined with "
                "distribute=True (the shard_map mesh) — drop one")

    def replace(self, **changes) -> "LUOptions":
        """A copy with ``changes`` applied (frozen-dataclass convenience)."""
        return dataclasses.replace(self, **changes)


@dataclasses.dataclass
class LUFactorization:
    """Numeric factors of one value set on a plan's structure.

    ``solve`` runs supernodal substitution + refinement on the packed
    factors (single (n,) or multi-RHS (n, k)); ``refactorize`` overwrites
    *this* factorization's buffers with a new value set (in-place reuse —
    the previous factors become invalid; use ``plan.factorize`` for
    independent factor objects).
    """

    plan: "LUPlan"
    num: NumericResult
    values: np.ndarray           # ORIGINAL values (refinement matvec)
    factor_s: float              # scatter + panel-sweep wall time
    # span summary of this factorization (tracing enabled only): the same
    # spans the Chrome trace carries, rendered as a text tree by ``str()``
    stats: Optional[SpanSummary] = None
    # the values actually swept: ``RobustPlan.transform_values(values)``
    # under static pivoting, ``values`` itself otherwise (same object)
    factored_values: Optional[np.ndarray] = None
    _quality: Optional[object] = dataclasses.field(default=None, repr=False)

    @property
    def n(self) -> int:
        return self.num.n

    @property
    def store(self) -> PanelStore:
        return self.num.store

    @property
    def l(self) -> np.ndarray:
        """Dense unit-lower L — test/oracle reconstruction helper."""
        return self.num.l

    @property
    def u(self) -> np.ndarray:
        """Dense upper U — test/oracle reconstruction helper."""
        return self.num.u

    def solve(self, b: np.ndarray, *, refine_iters: Optional[int] = None,
              refine_tol: Optional[float] = None,
              batched: Optional[bool] = None) -> SolveResult:
        """Solve A x = b on the existing factors.  ``b`` is (n,) or
        (n, k); refinement knobs default to the plan's ``LUOptions``.
        ``batched=None`` auto-picks the level-batched diagonal-solve path
        for multi-RHS ``b`` (one vmapped call per level-width group); the
        substitution sweeps keep the plan's per-device segments either
        way.  ``SolveResult.factor_s`` is 0.0 — the factorization time
        lives on this object's ``factor_s``."""
        opts = self.plan.options
        with _ot.ensure():
            return _solve(
                self.plan.a, b, values=self.values, num=self.num,
                refine_iters=(opts.refine_iters if refine_iters is None
                              else refine_iters),
                refine_tol=(opts.refine_tol if refine_tol is None
                            else refine_tol),
                batched=batched, transform=self.plan.robust)

    @property
    def perturbed_pivots(self) -> int:
        """Tiny pivots bumped by the robust tier during this sweep."""
        return self.num.perturbed_pivots

    def quality(self, *, itmax: int = 5):
        """Trust certificate of these factors (DESIGN.md §15): element
        growth, Hager 1-norm condition estimate of the factored system, and
        an "ok"/"suspect"/"reject" verdict.  A few triangular solves on the
        packed factors — computed lazily and cached on this object."""
        if self._quality is None:
            from repro.robust.condition import estimate_quality

            fvals = (self.factored_values if self.factored_values is not None
                     else self.values)
            self._quality = estimate_quality(
                self.num, self.plan.a_factored, fvals,
                perturbed_pivots=self.num.perturbed_pivots, itmax=itmax)
        return self._quality

    def refactorize(self, values: np.ndarray) -> "LUFactorization":
        """Factor a new value set **in place** on this factorization's
        buffers (zero + rescatter + panel sweep; no allocation)."""
        return self.plan.factorize(values, _reuse_store=self.num.store)


@dataclasses.dataclass
class BatchedLUFactorization:
    """Factors of B same-pattern value sets in one batched sweep
    (DESIGN.md §14) — the many-matrix tier of the session API.

    ``solve_batch`` runs the substitution level sweeps + iterative
    refinement across all B systems at once; ``system(i)`` exposes system
    i as an ordinary ``LUFactorization`` over zero-copy views of the
    batched buffers, so everything downstream of the sequential API
    (solve, dense oracle reconstruction) works per system.  Every per-
    system result is bitwise-identical to the sequential
    ``plan.factorize(values_batch[i])`` / ``.solve(b[i])`` loop.
    """

    plan: "LUPlan"
    num: BatchedNumericResult
    values: np.ndarray           # (B, nnz) ORIGINAL values
    factor_s: float              # scatter + batched panel-sweep wall time
    stats: Optional[SpanSummary] = None
    factored_values: Optional[np.ndarray] = None   # (B, nnz) swept values

    @property
    def batch(self) -> int:
        return self.num.batch

    @property
    def n(self) -> int:
        return self.num.n

    @property
    def store(self) -> BatchedPanelStore:
        return self.num.store

    @property
    def perturbed_pivots(self) -> np.ndarray:
        """Per-system tiny-pivot bump counts, (B,) int64 (all zero unless
        the plan was built with ``LUOptions(perturb=True)``)."""
        pp = self.num.perturbed_pivots
        return (pp if pp is not None
                else np.zeros(self.batch, dtype=np.int64))

    def system(self, i: int) -> LUFactorization:
        """System i as a sequential ``LUFactorization`` (zero-copy factor
        views; its ``factor_s`` is 0.0 — the batch owns the timing)."""
        return LUFactorization(
            plan=self.plan, num=self.num.system(i),
            values=self.values[i], factor_s=0.0,
            factored_values=(self.factored_values[i]
                             if self.factored_values is not None else None))

    def solve_batch(self, b: np.ndarray, *,
                    refine_iters: Optional[int] = None,
                    refine_tol: Optional[float] = None
                    ) -> BatchedSolveResult:
        """Solve A_i x_i = b_i for every system on the existing factors.
        ``b`` is (B, n) or (B, n, k); refinement knobs default to the
        plan's ``LUOptions``.  Refinement masks per system, so each
        system's solution and residual history match the sequential
        ``factor.solve`` loop bitwise."""
        opts = self.plan.options
        with _ot.ensure():
            return _solve_batch(
                self.plan.a, b, self.values, self.num,
                refine_iters=(opts.refine_iters if refine_iters is None
                              else refine_iters),
                refine_tol=(opts.refine_tol if refine_tol is None
                            else refine_tol),
                transform=self.plan.robust)


@dataclasses.dataclass
class LUPlan:
    """One matrix structure, analyzed once: the symbolic prediction plus
    every value-independent precomputation of the numeric pipeline.

    Plans are picklable (numpy arrays + plain dataclasses only), so an
    analysis can be computed in one process and reused in many — the
    refactorization server pattern.  ``factorize(values)`` is the only
    per-value work: O(nnz) scatter + the level-scheduled panel sweep.
    """

    a: CSRMatrix
    options: LUOptions
    sym: SymbolicResult
    pattern: CSCPattern
    schedule: PanelSchedule
    store_template: PanelStore
    gather_maps: List
    csr_maps: CsrScatterMaps
    solve_schedule: SolveSchedule
    analyze_s: float
    # device placement of panel work (DESIGN.md §11): plain numpy, so the
    # plan pickles; the mesh itself is never stored — rebuild one with
    # ``launch.mesh.make_flat_mesh`` where live devices are needed
    placement: Optional[PanelPlacement] = None
    # span summary of the analyze that built this plan (tracing enabled
    # only); picklable like everything else on the plan
    stats: Optional[SpanSummary] = None
    # static-pivoting state (DESIGN.md §15, ``LUOptions(pivot="static")``):
    # the ``RobustPlan`` transform and the permuted structural matrix the
    # symbolic analysis actually ran on.  Plain numpy — the plan pickles.
    robust: Optional[object] = None
    factored: Optional[CSRMatrix] = None
    # autotune record (DESIGN.md §16, ``LUOptions(autotune=True)``): the
    # ``tune.TuneReport`` whose chosen knob values are frozen into
    # ``options`` — picklable, so a loaded plan replays without re-tuning
    tuned: Optional[object] = None

    @property
    def a_factored(self) -> CSRMatrix:
        """The structural matrix the factors describe: ``Dr·P·A·Dc``'s
        pattern under static pivoting, ``a`` itself otherwise."""
        return self.factored if self.factored is not None else self.a

    @property
    def n(self) -> int:
        return self.a.n

    @property
    def n_devices(self) -> int:
        return self.placement.n_devices if self.placement is not None else 1

    @property
    def lu_nnz(self) -> int:
        """Predicted structural nonzeros of L+U (diagonal included)."""
        return self.pattern.nnz

    @property
    def n_supernodes(self) -> int:
        return self.schedule.n_panels

    @property
    def n_levels(self) -> int:
        return self.schedule.n_levels

    def place(self, n_devices: Optional[int] = None, *,
              policy: str = "lpt") -> "LUPlan":
        """Re-derive the panel placement for ``n_devices`` (DESIGN.md §13).

        Placement is a *derived* property of the schedule, not a frozen
        analyze-time fact: re-binning every dependency level's panels via
        ``numeric.schedule.build_placement`` adapts a pickled plan to
        whatever mesh exists where it is loaded — a plan analyzed at D=8
        runs on 1, 2, or 200 devices.  ``n_devices=None`` takes the
        visible device count (``launch.mesh.visible_device_count``).
        Within a level panels are independent, so placement changes
        scheduling only — factors and solutions stay bitwise-identical at
        every count.  Returns ``self`` (placement is replaced in place) so
        ``pickle.load(f).place().factorize(v)`` chains.
        """
        if n_devices is None:
            from repro.launch.mesh import visible_device_count

            n_devices = visible_device_count()
        from repro.launch.mesh import FLAT_AXIS

        self.placement = build_placement(self.schedule, n_devices,
                                         axis=FLAT_AXIS, policy=policy)
        return self

    def factorize(self, values: Optional[np.ndarray] = None, *,
                  _reuse_store: Optional[PanelStore] = None
                  ) -> LUFactorization:
        """Numeric factorization of ``values`` (CSR-aligned (nnz,) or dense
        (n, n); defaults to ``generic_values_csr``) on the precomputed
        structure — no schedule/store/map reconstruction.  Bitwise-identical
        factors to one-shot ``numeric_factorize`` on the same inputs."""
        t0 = time.perf_counter()
        if values is None:
            values = generic_values_csr(self.a)
        values = np.asarray(values, dtype=np.float64)
        if self.robust is not None:
            # replay the static-pivoting transform: O(nnz) gather + scale
            # (value-only — no symbolic work on refactorize)
            fvals = (self.robust.transform_dense(values) if values.ndim == 2
                     else self.robust.transform_values(values))
        else:
            fvals = values
        store = (_reuse_store if _reuse_store is not None
                 else PanelStore.from_structure(self.store_template))
        store._solve_schedule = self.solve_schedule
        store._placement = self.placement       # per-device solve segments
        with _ot.ensure(self.options.trace) as tr:
            mark = tr.mark() if tr is not None else 0
            with _ot.span("factorize"):
                num = factor_on_store(
                    self.a_factored, fvals, store, self.schedule,
                    backend=self.options.numeric_backend,
                    piv_tol=self.options.piv_tol,
                    check_pattern=self.options.check_pattern,
                    pattern_tol=self.options.pattern_tol,
                    maps=self.gather_maps, csr_maps=self.csr_maps,
                    store_is_zeroed=_reuse_store is None,
                    placement=self.placement,
                    segment_batch=self.options.segment_batch,
                    perturb=self.options.perturb,
                    perturb_eps=self.options.perturb_eps)
            stats = tr.summary(mark) if tr is not None else None
        return LUFactorization(plan=self, num=num, values=values,
                               factor_s=time.perf_counter() - t0,
                               stats=stats, factored_values=fvals)

    def factorize_batch(self, values_batch: np.ndarray
                        ) -> BatchedLUFactorization:
        """Numeric factorization of B same-pattern value sets in ONE
        batched level sweep (DESIGN.md §14): ``values_batch`` is a
        (B, nnz) CSR-aligned stack; every per-panel operation of the
        sweep broadcasts over the leading system axis, so the per-call
        Python/scheduling overhead is paid once for the whole batch —
        the circuit-simulation regime (Newton iterations, transient
        sweeps, Monte Carlo corners sharing one pattern).

        System i's factors are bitwise-identical to
        ``self.factorize(values_batch[i])`` — property-tested across
        every ``sparse/matrices.py`` generator."""
        t0 = time.perf_counter()
        values_batch = np.asarray(values_batch, dtype=np.float64)
        if values_batch.ndim != 2:
            raise ValueError(
                f"values_batch must be a (B, {self.a.nnz}) CSR-aligned "
                f"stack, got shape {values_batch.shape}")
        fvals_batch = (self.robust.transform_values(values_batch)
                       if self.robust is not None else values_batch)
        bstore = BatchedPanelStore(self.store_template,
                                   values_batch.shape[0])
        # solve_batch levels come from the plan, cached where the batched
        # substitution looks for them (the shared structure template)
        self.store_template._solve_schedule = self.solve_schedule
        with _ot.ensure(self.options.trace) as tr:
            mark = tr.mark() if tr is not None else 0
            with _ot.span("factorize_batch"):
                num = factor_batch_on_store(
                    self.a_factored, fvals_batch, bstore, self.schedule,
                    backend=self.options.numeric_backend,
                    piv_tol=self.options.piv_tol,
                    check_pattern=self.options.check_pattern,
                    pattern_tol=self.options.pattern_tol,
                    maps=self.gather_maps, csr_maps=self.csr_maps,
                    store_is_zeroed=True,
                    perturb=self.options.perturb,
                    perturb_eps=self.options.perturb_eps)
            stats = tr.summary(mark) if tr is not None else None
        return BatchedLUFactorization(plan=self, num=num,
                                      values=values_batch,
                                      factor_s=time.perf_counter() - t0,
                                      stats=stats,
                                      factored_values=fvals_batch)

    def solve(self, b: np.ndarray,
              values: Optional[np.ndarray] = None) -> SolveResult:
        """Convenience: factorize ``values`` and solve in one call (the
        result's ``factor_s``/``solve_s`` split stays honest)."""
        factor = self.factorize(values)
        res = factor.solve(b)
        res.factor_s = factor.factor_s
        return res


def _partition_with_blocking(pattern, supernodes, fingerprints, opts,
                             peaks):
    """Apply autotune / structure-aware blocking to a detected partition.

    Returns ``(supernodes, tuned, opts)``: the (possibly merged) partition,
    the ``TuneReport`` when autotuning ran, and the options with any chosen
    knob values frozen in.  A no-op (same objects back) when both knobs are
    off — the default path never touches the new code.
    """
    tuned = None
    if opts.autotune:
        from repro.tune import autotune_partition

        supernodes, tuned = autotune_partition(pattern, fingerprints, opts,
                                               peaks=peaks)
        opts = opts.replace(**tuned.chosen)
    elif opts.blocking:
        from repro.supernodes.blocking import merge_supernodes
        from repro.tune import cost_model_for

        threshold = (1.0 if opts.block_merge_threshold is None
                     else opts.block_merge_threshold)
        supernodes, _ = merge_supernodes(
            pattern, supernodes, cost_model_for(opts, peaks),
            threshold=threshold, max_width=opts.block_max_width)
    return supernodes, tuned, opts


def analyze(a: CSRMatrix, options: Optional[LUOptions] = None, *,
            values: Optional[np.ndarray] = None,
            mesh=None, on_progress=None, peaks=None) -> LUPlan:
    """Symbolic analysis of ``a``: one fixpoint pass streams out the L/U
    counts, the supernode partition (fingerprints), and the sparse
    ``CSCPattern``; everything value-independent downstream (schedules,
    row-index gather maps, CSR scatter maps, store structure, solve DAGs)
    is precomputed into the returned ``LUPlan``.

    ``mesh`` (a ``jax.sharding.Mesh``; ``launch.mesh.make_flat_mesh``
    builds the flat one) shards the fixpoint's sources over the mesh
    devices inside shard_map and attaches a ``PanelPlacement`` that splits
    every level's panel work into per-device segments (DESIGN.md §11).
    ``LUOptions(distribute=True)`` builds the all-device flat mesh
    automatically.  The same code path runs at every device count —
    counts, supernodes, pattern, factors, and solutions are
    bitwise-identical to the mesh-less analysis, and the plan still
    pickles (it stores the placement, never the mesh).

    This never materializes a dense (n, n) pattern on the host *or on any
    shard* — memory stays O(nnz(L+U)) plus the streamed chunk masks, so
    it scales to the packed numeric path's n (tens of thousands and up).

    With ``LUOptions(pivot="static")`` the robust pre-pass runs first
    (DESIGN.md §15): a maximum-product transversal over ``values``
    (a *representative* value set — defaults to ``generic_values_csr(a)``,
    which weights pattern structure only; pass real values for
    value-informed pivoting) picks the row permutation, Ruiz equilibration
    the scalings, and the symbolic fixpoint + everything downstream run on
    the permuted pattern.  The transform is a plan property
    (``LUPlan.robust``), so refactorization remains a value-only O(nnz)
    gather + scale.

    With ``LUOptions(blocking=True)`` / ``LUOptions(autotune=True)`` the
    detected supernode partition additionally runs through the
    structure-aware blocking merge pass / roofline knob sweep (DESIGN.md
    §16) before schedules and storage are built; ``peaks`` optionally
    feeds the cost model a probed ``benchmarks/roofline.py``
    ``machine_peaks()`` dict (fixed representative constants otherwise, so
    tuning stays deterministic).  ``repro.replan`` re-derives all of this
    on an existing plan without re-running the fixpoint.
    """
    t0 = time.perf_counter()
    opts = options if options is not None else LUOptions()
    if mesh is None and opts.distribute:
        from repro.launch.mesh import make_flat_mesh

        mesh = make_flat_mesh()
    robust = None
    a_sym = a
    with _ot.ensure(opts.trace) as tr:
        mark = tr.mark() if tr is not None else 0
        if opts.pivot == "static":
            from repro.robust import build_robust_prepass

            with _ot.span("robust_prepass"):
                pivot_values = (values if values is not None
                                else generic_values_csr(a))
                a_sym, robust = build_robust_prepass(a, pivot_values)
        with _ot.span("analyze"):
            sym = _symbolic_factorize(
                a_sym, concurrency=opts.concurrency, backend=opts.backend,
                combined=opts.combined, bubble=opts.bubble,
                use_arena=opts.use_arena, budget_bytes=opts.budget_bytes,
                checkpoint_path=opts.checkpoint_path,
                detect_supernodes=True,
                supernode_relax=opts.supernode_relax,
                supernode_max_size=opts.supernode_max_size,
                collect_pattern=True, mesh=mesh, runtime=opts.runtime,
                on_progress=on_progress)
            pattern = sym.pattern
            supernodes, tuned, opts = _partition_with_blocking(
                pattern, sym.supernodes, sym.fingerprints, opts, peaks)
            with _ot.span("build_schedule"):
                schedule = build_schedule(pattern, supernodes,
                                          n_bins=opts.n_bins,
                                          policy=opts.policy)
                store_template = PanelStore(pattern, schedule.supernodes)
            with _ot.span("gather_maps"):
                gather_maps = build_gather_maps(store_template, schedule)
                csr_maps = store_template.csr_maps(a_sym)
            with _ot.span("solve_schedule"):
                solve_schedule = build_solve_schedule(store_template)
            placement = None
            if mesh is not None:
                n_devices = int(np.prod(list(mesh.shape.values())))
                placement = build_placement(schedule, n_devices,
                                            axis=mesh.axis_names[0])
            elif opts.runtime == "dynamic":
                # the dynamic runtime drove every visible device through
                # the analyze; give factorize/solve the matching per-device
                # segments (re-derivable later at any count via ``place``)
                from repro.launch.mesh import FLAT_AXIS, visible_device_count

                placement = build_placement(schedule,
                                            visible_device_count(),
                                            axis=FLAT_AXIS)
        stats = tr.summary(mark) if tr is not None else None
    return LUPlan(a=a, options=opts, sym=sym, pattern=pattern,
                  schedule=schedule, store_template=store_template,
                  gather_maps=gather_maps, csr_maps=csr_maps,
                  solve_schedule=solve_schedule,
                  analyze_s=time.perf_counter() - t0,
                  placement=placement, stats=stats,
                  robust=robust,
                  factored=a_sym if robust is not None else None,
                  tuned=tuned)


def replan(plan: LUPlan, options: Optional[LUOptions] = None, *,
           peaks=None) -> LUPlan:
    """Re-derive a plan under new partition knobs WITHOUT re-running the
    symbolic fixpoint (DESIGN.md §16).

    The expensive part of ``analyze`` is the label fixpoint; the supernode
    partition, schedules, gather/scatter maps, storage template, and solve
    DAGs are all cheap derivations from the retained O(n) column
    fingerprints and the sparse pattern.  ``replan`` re-runs exactly those
    derivations for ``options`` (defaults to the plan's own) — including
    the blocking merge pass and the autotune sweep — so comparing blocked
    vs. unblocked partitions, or autotuning a plan analyzed with defaults,
    costs seconds instead of the full analyze.  Returns a NEW independent
    ``LUPlan`` (the input plan is untouched); with knobs equal to the
    plan's own, the result factorizes bitwise-identically.

    Placement is re-derived at the plan's device count when one exists.
    Raises ``ValueError`` for plans pickled before fingerprint retention
    (pre-v1.7.0).
    """
    t0 = time.perf_counter()
    opts = options if options is not None else plan.options
    fp = getattr(plan.sym, "fingerprints", None)
    if fp is None:
        raise ValueError(
            "plan retains no column fingerprints (analyzed before v1.7.0, "
            "or symbolic ran without supernode detection); re-run "
            "repro.analyze() to rebuild it")
    pattern = plan.pattern
    with _ot.ensure(opts.trace) as tr:
        mark = tr.mark() if tr is not None else 0
        with _ot.span("replan"):
            from repro.supernodes.detect import detect_from_fingerprints

            supernodes = detect_from_fingerprints(
                fp, relax=opts.supernode_relax,
                max_size=opts.supernode_max_size)
            supernodes, tuned, opts = _partition_with_blocking(
                pattern, supernodes, fp, opts, peaks)
            with _ot.span("build_schedule"):
                schedule = build_schedule(pattern, supernodes,
                                          n_bins=opts.n_bins,
                                          policy=opts.policy)
                store_template = PanelStore(pattern, schedule.supernodes)
            with _ot.span("gather_maps"):
                gather_maps = build_gather_maps(store_template, schedule)
                csr_maps = store_template.csr_maps(plan.a_factored)
            with _ot.span("solve_schedule"):
                solve_schedule = build_solve_schedule(store_template)
            placement = None
            if plan.placement is not None:
                placement = build_placement(schedule,
                                            plan.placement.n_devices,
                                            axis=plan.placement.axis)
        stats = tr.summary(mark) if tr is not None else None
    return LUPlan(a=plan.a, options=opts, sym=plan.sym, pattern=pattern,
                  schedule=schedule, store_template=store_template,
                  gather_maps=gather_maps, csr_maps=csr_maps,
                  solve_schedule=solve_schedule,
                  analyze_s=plan.analyze_s + (time.perf_counter() - t0),
                  placement=placement, stats=stats,
                  robust=plan.robust, factored=plan.factored,
                  tuned=tuned)
