"""Newton steps on one plan, closed loop with one caller.

The pattern is the configuration's at the mix's ``pattern_seed``, the
same circuit in every run, so every seed does the same work; ``--seed``
draws the values and right-hand sides.  Set-up analyzes it once and warms
up ``warmup_steps`` steps.  One unit is one Newton step: fresh values (the
seed's base values scaled entrywise by ``1 + value_jitter * u``, u uniform
on [-1, 1]) and a fresh right-hand side, then ``plan.factorize(values)``
and ``factor.solve(b)``.  After the window every step's solution is checked
by its residual, in float64, against the values and right-hand side it was
given.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from bench.lib import patterns as P
from bench.lib import reference as R

WARMUP_STEP0 = 1 << 40      # warm-up steps draw from their own step ids


class Run:
    def __init__(self, ctx):
        import repro
        from repro.sparse.csr import CSRMatrix

        cfg, mix = ctx.cell.config, ctx.cell.traffic
        self.seed, self.jitter = ctx.seed, mix["value_jitter"]
        self.limit = cfg["residual_limit"]
        self.p = P.generate(cfg, mix["pattern_seed"])
        options = dict(cfg["options"], **mix.get("options", {}))
        with jax.profiler.TraceAnnotation("bench.analyze"):
            self.plan = repro.analyze(
                CSRMatrix(self.p.n, self.p.indptr, self.p.indices),
                repro.LUOptions(**options))
        self.base = P.base_values(self.p, ctx.seed)
        self._one = jax.device_put(jnp.zeros((), jnp.float32),
                                   ctx.devices[0])
        self._bump = jax.jit(lambda x: x + 1)
        self.x, self.factorize_s, self.solve_s = [], [], []
        for w in range(mix["warmup_steps"]):
            self._step(WARMUP_STEP0 + w)
        self.x, self.factorize_s, self.solve_s = [], [], []

    def inputs(self, step: int):
        return (P.step_values(self.base, self.seed, step, self.jitter),
                P.rhs(self.p.n, self.seed, step))

    def _sync(self) -> None:
        """Return once the device has run everything enqueued before."""
        self._one = self._bump(self._one)
        self._one.block_until_ready()

    def _step(self, step: int) -> None:
        values, b = self.inputs(step)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.factorize"):
            factor = self.plan.factorize(values)
            self._sync()
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.solve"):
            x = factor.solve(b).x
            self._sync()
        t2 = time.perf_counter()
        self.x.append(x)
        self.factorize_s.append(t1 - t0)
        self.solve_s.append(t2 - t1)

    unit = _step

    def end_to_end(self, ctx) -> dict:
        return {"newton_step_s": ctx.window_s / ctx.units}

    def check(self):
        res = []
        for i, x in enumerate(self.x):
            values, b = self.inputs(i)
            res.append(R.residual(self.p, values, x, b))
        failed = sum(r > self.limit for r in res)
        return ({"steps_residual_max": {"value": max(res),
                                        "limit": self.limit}}, failed)
