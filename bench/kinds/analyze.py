"""Full analyses, closed loop with one caller, a new labelling every unit.

Set-up builds the configuration's pattern and runs ``warmup_units``
analyses, which compile every program the window runs.  Unit ``i`` is one
``repro.analyze`` of the pattern under labelling ``i`` drawn from the seed
(``bench.lib.patterns.Relabeller``): the same size and elimination work
each time, so no program compiles in the window, and a pattern never seen
before, so a plan made earlier cannot answer it.  The unit keeps what the
check compares (the L+U pattern, row counts, supernode partition) and
drops the plan, except for one drawn from the seed by reservoir sampling.

After the window every unit's outputs are compared with the plain
symbolic reference of its own pattern, and the drawn plan factorizes the
seed's values of its pattern and solves; its solution is checked by its
residual.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import scipy.sparse as sp

from bench.lib import patterns as P
from bench.lib import reference as R

CHECK_STREAM = 9
WARMUP_UNIT0 = 1 << 40      # warm-up units draw labellings of their own
PLAN_SPANS = ("build_schedule", "gather_maps", "solve_schedule")


@dataclasses.dataclass
class Outputs:
    """What the check compares of one unit's plan."""

    unit: int
    indptr: np.ndarray
    rowind: np.ndarray
    l_counts: np.ndarray
    u_counts: np.ndarray
    supernodes: np.ndarray
    plan_build_s: float | None   # LUOptions(trace=True) runs only


class Run:
    def __init__(self, ctx):
        import repro
        from repro.sparse.csr import CSRMatrix

        cfg, mix = ctx.cell.config, ctx.cell.traffic
        self.seed, self.limit = ctx.seed, cfg["residual_limit"]
        self.max_size = cfg["options"]["supernode_max_size"]
        self.labels = P.Relabeller(cfg, ctx.seed)
        self.options = repro.LUOptions(
            **dict(cfg["options"], **mix.get("options", {}),
                   trace=ctx.trace))
        self._analyze, self._csr = repro.analyze, CSRMatrix
        self._pick, self.outputs = P.rng(ctx.seed, CHECK_STREAM), []
        for w in range(mix["warmup_units"]):
            self.unit(WARMUP_UNIT0 + w)
        self.outputs, self.kept = [], None
        self._pick = P.rng(ctx.seed, CHECK_STREAM)

    def unit(self, i: int) -> None:
        p = self.labels(i)
        with jax.profiler.TraceAnnotation("bench.analyze"):
            plan = self._analyze(self._csr(p.n, p.indptr, p.indices),
                                 self.options)
        build = None
        if plan.stats is not None:
            build = sum(node.total_s for node in map(plan.stats.find,
                                                     PLAN_SPANS) if node)
        self.outputs.append(Outputs(
            unit=i, indptr=plan.pattern.indptr, rowind=plan.pattern.rowind,
            l_counts=plan.sym.l_counts, u_counts=plan.sym.u_counts,
            supernodes=plan.sym.supernodes, plan_build_s=build))
        # reservoir sampling: every unit is kept with equal chance
        if self._pick.random() * len(self.outputs) < 1.0:
            self.kept = (len(self.outputs) - 1, p, plan)

    def end_to_end(self, ctx) -> dict:
        return {"analyze_s": ctx.window_s / ctx.units}

    def check(self):
        pattern = counts = snodes = 0
        bad = []
        for out in self.outputs:
            ref = R.symbolic_lu(self.labels(out.unit))
            ref_sn = R.supernodes(ref, max_size=self.max_size)
            d_pat = (_csc(ref.n, out.indptr, out.rowind)
                     != _csc(ref.n, ref.indptr, ref.rowind)).nnz
            d_cnt = int(np.sum(out.l_counts != ref.l_counts)
                        + np.sum(out.u_counts != ref.u_counts))
            d_sn = _range_diff(out.supernodes, ref_sn)
            pattern, counts, snodes = (pattern + d_pat, counts + d_cnt,
                                       snodes + d_sn)
            bad.append(bool(d_pat or d_cnt or d_sn))
        # the drawn plan factorizes and solves on the float64 host sweep,
        # which reads every structure analyze built for the plan
        j, p, plan = self.kept
        plan = dataclasses.replace(
            plan, options=plan.options.replace(numeric_backend="numpy"))
        values = P.base_values(p, self.seed)
        b = P.rhs(p.n, self.seed, 0)
        x = plan.factorize(values).solve(b).x
        res = R.residual(p, values, x, b)
        bad[j] = bad[j] or res > self.limit
        return ({"pattern_entries_differing": {"value": pattern, "limit": 0},
                 "row_counts_differing": {"value": counts, "limit": 0},
                 "supernode_bounds_differing": {"value": snodes, "limit": 0},
                 "plan_solve_residual": {"value": res, "limit": self.limit}},
                sum(bad))


def _csc(n, indptr, rowind):
    return sp.csc_matrix((np.ones(len(rowind), dtype=np.int8), rowind,
                          indptr), shape=(n, n))


def _range_diff(a: np.ndarray, b: np.ndarray) -> int:
    """Supernode boundaries in one partition and not the other."""
    sa = set(np.asarray(a)[:, 0].tolist())
    sb = set(np.asarray(b)[:, 0].tolist())
    return len(sa ^ sb)
