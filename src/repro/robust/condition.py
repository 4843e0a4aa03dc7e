"""Cheap factorization-quality estimates: element growth, Hager 1-norm
condition, and a trust verdict (DESIGN.md §15).

A no-pivot (statically pivoted, possibly perturbed) factorization can
*complete* and still be garbage — the whole point of static pivoting is
trading the per-column pivot search for a post-hoc certificate.  This
module computes that certificate from quantities the packed factors
already hold:

* **Element growth** ``max|L\\U| / max|A_f|`` — the classic stability
  proxy (Wilkinson): large growth means elimination amplified roundoff and
  the backward error bound is weak.
* **Hager/Higham 1-norm condition estimate** — ``cond_1(A_f) ~
  ‖A_f‖₁ · est(‖A_f^{-1}‖₁)`` where the inverse norm comes from a few
  forward/transpose solves on the existing packed factors (each iterate is
  one ``solve_factored`` + one ``solve_factored_transposed``; never a
  dense inverse).  This is the LAPACK ``gecon`` algorithm, O(nnz) per
  iterate.
* **Verdict** — "ok" / "suspect" / "reject" from fixed thresholds, so
  serving-path callers (``repro.serve``) can gate answers without
  interpreting raw numbers.  The estimates describe the FACTORED system
  ``A_f = Dr·P·A·Dc`` — after equilibration that is exactly the system
  whose conditioning decides how much accuracy refinement can recover.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.numeric.solve import solve_factored, solve_factored_transposed
from repro.obs import trace as _ot

#: Verdict thresholds.  cond_1 beyond ~1e10 leaves <6 float64 digits for
#: refinement to work with ("suspect"); beyond ~1e14 essentially none
#: ("reject").  Growth mirrors the same margins on the Wilkinson proxy.
COND_SUSPECT = 1e10
COND_REJECT = 1e14
GROWTH_SUSPECT = 1e6
GROWTH_REJECT = 1e10


@dataclasses.dataclass(frozen=True)
class QualityReport:
    """Trust certificate of one factorization (``LUFactorization.quality()``).

    ``verdict`` is "ok", "suspect" (perturbed pivots or moderate
    growth/conditioning — check the achieved residual before trusting), or
    "reject" (non-finite or hopeless conditioning — the solve should not be
    trusted even if it returns numbers).
    """

    growth: float              # max|L\U| / max|A_f| element growth
    cond_1_est: float          # Hager estimate of cond_1(A_f)
    norm1_a: float             # ‖A_f‖₁ (exact, from the factored values)
    perturbed_pivots: int      # tiny pivots bumped during the sweep
    verdict: str               # "ok" | "suspect" | "reject"

    @property
    def ok(self) -> bool:
        return self.verdict == "ok"


def _verdict(growth: float, cond: float, perturbed: int) -> str:
    if (not np.isfinite(growth) or not np.isfinite(cond)
            or cond > COND_REJECT or growth > GROWTH_REJECT):
        return "reject"
    if perturbed > 0 or cond > COND_SUSPECT or growth > GROWTH_SUSPECT:
        return "suspect"
    return "ok"


def condest_1(num, norm1_a: float, *, itmax: int = 5) -> float:
    """Hager/Higham estimate of ``cond_1`` of the factored matrix:
    ``norm1_a * est(‖A_f^{-1}‖₁)`` via at most ``itmax`` rounds of one
    factored solve + one transposed solve each (the gecon iteration).
    The estimate is a lower bound, in practice within a small factor of
    the true norm."""
    n = num.n
    if n == 0:
        return 0.0
    x = np.full(n, 1.0 / n)
    est = 0.0
    last_j = -1
    for _ in range(max(1, itmax)):
        y = solve_factored(num, x, batched=False)
        est = float(np.abs(y).sum())
        if not np.isfinite(est):
            return np.inf
        xi = np.where(y >= 0.0, 1.0, -1.0)
        z = solve_factored_transposed(num, xi)
        j = int(np.argmax(np.abs(z)))
        if float(np.abs(z[j])) <= float(z @ x) or j == last_j:
            break
        x = np.zeros(n)
        x[j] = 1.0
        last_j = j
    return est * norm1_a


def element_growth(num, factored_scale: float) -> float:
    """``max|L\\U| / max|A_f|`` over the packed blocks (padding is zeroed
    by the sweep, so the block max IS the factor max)."""
    gmax = 0.0
    for blk in num.store.blocks:
        if blk.size:
            m = float(np.abs(blk).max())
            if not np.isfinite(m):
                return np.inf
            gmax = max(gmax, m)
    return gmax / factored_scale if factored_scale > 0.0 else 0.0


def norm1_csr(a, factored_values: np.ndarray) -> float:
    """Exact ‖A_f‖₁ (max column abs-sum) from CSR-aligned values, O(nnz)."""
    sums = np.zeros(a.n, dtype=np.float64)
    np.add.at(sums, a.indices.astype(np.int64), np.abs(factored_values))
    return float(sums.max()) if a.n else 0.0


def estimate_quality(num, a_f, factored_values: np.ndarray, *,
                     perturbed_pivots: int = 0,
                     itmax: int = 5) -> QualityReport:
    """Compute the full certificate for one factorization.

    ``num``: the ``NumericResult`` holding the packed factors;
    ``a_f``/``factored_values``: the structural matrix and CSR-aligned
    values that were factored (the transformed system when static pivoting
    is on, the original otherwise).
    """
    with _ot.span("robust_quality"):
        values = np.asarray(factored_values, dtype=np.float64)
        if values.ndim == 2:
            norm1 = float(np.abs(values).sum(axis=0).max()) if values.size \
                else 0.0
            scale = float(np.abs(values).max()) if values.size else 0.0
        else:
            norm1 = norm1_csr(a_f, values)
            scale = float(np.abs(values).max()) if values.size else 0.0
        growth = element_growth(num, scale)
        cond = condest_1(num, norm1, itmax=itmax)
        report = QualityReport(growth=growth, cond_1_est=cond, norm1_a=norm1,
                               perturbed_pivots=int(perturbed_pivots),
                               verdict=_verdict(growth, cond,
                                                int(perturbed_pivots)))
    return report
