"""The check that decides ``correct``, shown to fail.

Each test drives a whole run of a cell, at a size a CPU test can hold and
with the look for a chip skipped, through ``run_cell.run_cell``.  A sound
run comes out correct; the control (the plain reference in the program's
place, in float32, the precision below the configuration's float64) and
each planted fault of the timed path come out not correct.

    python -m pytest bench/tests
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import run_cell as rc
from bench.lib import patterns as P
from bench.lib import reference as R

SMALL = {"bordered_block_diagonal": {"n": 1200}, "grid3d_laplacian": {"nx": 6}}
SEED = 2 ** 33 + 5
CELLS_1 = ["bbd-20k.newton", "poisson3d-16.analyze", "bbd-20k.analyze"]


def small(name: str) -> rc.Cell:
    cell = rc.Cell.load(name)
    cell.config["args"].update(SMALL[cell.config["generator"]])
    return cell


def run(name: str, seconds: float = 0.3) -> dict:
    import jax

    return rc.run_cell(small(name), SEED, seconds, False, jax.devices())


def _reference_f32_solve(self, b, **_kw):
    """The control: the reference solve, in float32, in place of the
    program's ``LUFactorization.solve``."""
    a = self.plan.a
    p = P.Pattern(a.n, a.indptr, a.indices)
    return types.SimpleNamespace(
        x=R.solve(p, np.asarray(self.values), b, dtype=np.float32))


@pytest.mark.parametrize("name", CELLS_1)
def test_sound_run_is_correct(name):
    res = run(name)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("name", CELLS_1)
def test_control_is_not_correct(name, monkeypatch):
    import repro.api as api

    monkeypatch.setattr(api.LUFactorization, "solve", _reference_f32_solve)
    res = run(name)
    assert res["correct"] is False, res["checks"]


def test_stale_factorization_is_not_correct(monkeypatch):
    """A step that returns its state unchanged: every factorize hands back
    the first factorization it made."""
    import repro.api as api

    orig, first = api.LUPlan.factorize, []

    def stale(self, values=None, **kw):
        if not first:
            first.append(orig(self, values, **kw))
        return first[0]

    monkeypatch.setattr(api.LUPlan, "factorize", stale)
    res = run("bbd-20k.newton")
    assert res["correct"] is False and res["failed"] == res["attempted"]


def test_altered_solution_is_not_correct(monkeypatch):
    """An answer altered where it is produced: one entry of x off by one
    part in a million."""
    import repro.api as api

    orig = api.LUFactorization.solve

    def altered(self, b, **kw):
        res = orig(self, b, **kw)
        res.x[len(res.x) // 2] *= 1.0 + 1e-6
        return res

    monkeypatch.setattr(api.LUFactorization, "solve", altered)
    assert run("bbd-20k.newton")["correct"] is False


def _drop_one_fill_entry(plan):
    """The plan with one off-diagonal entry taken out of its L+U pattern."""
    from repro.numeric.storage import CSCPattern

    pat = plan.pattern
    j = int(np.flatnonzero(np.diff(pat.indptr) > 1)[0])
    k = int(pat.indptr[j]) + (pat.rowind[pat.indptr[j]] == j)
    indptr = pat.indptr.copy()
    indptr[j + 1:] -= 1
    return dataclasses.replace(plan, pattern=CSCPattern(
        pat.n, indptr, np.delete(pat.rowind, k)))


def _half_the_sources(plan):
    """The plan as if every other source's row had never been run."""
    sym = plan.sym
    l, u = sym.l_counts.copy(), sym.u_counts.copy()
    l[1::2] = 0
    u[1::2] = 0
    return dataclasses.replace(plan, sym=dataclasses.replace(
        sym, l_counts=l, u_counts=u))


@pytest.mark.parametrize("fault", [_drop_one_fill_entry, _half_the_sources])
@pytest.mark.parametrize("name", ["poisson3d-16.analyze", "bbd-20k.analyze"])
def test_faulty_analyze_is_not_correct(name, fault, monkeypatch):
    import repro

    orig = repro.analyze
    monkeypatch.setattr(repro, "analyze",
                        lambda a, options=None, **kw: fault(orig(a, options,
                                                                 **kw)))
    res = run(name)
    assert res["correct"] is False and res["failed"] == res["attempted"]


@pytest.mark.parametrize("name", ["poisson3d-16.analyze", "bbd-20k.analyze"])
def test_reused_plan_is_not_correct(name, monkeypatch):
    """A step that returns its state unchanged: analyze hands back the
    first plan it made (in set-up), whatever pattern it is given.  In bbd
    every unit's fill differs from it; in the RCM grid the fill is the
    same in every labelling (RCM's envelope), so the drawn plan's solve,
    whose matrix differs, fails."""
    import repro

    orig, first = repro.analyze, []

    def memo(a, options=None, **kw):
        if not first:
            first.append(orig(a, options, **kw))
        return first[0]

    monkeypatch.setattr(repro, "analyze", memo)
    res = run(name)
    assert res["correct"] is False and res["failed"] >= 1
    if name.startswith("bbd"):
        assert res["failed"] == res["attempted"]


@pytest.mark.parametrize("name", ["poisson3d-16.analyze", "bbd-20k.analyze"])
def test_relabelled_units_differ_and_keep_their_work(name):
    """Every unit's pattern is new, and its fill and supernodes are as
    large as the configuration's own."""
    cfg = small(name).config
    labels = P.Relabeller(cfg, SEED)
    base = R.symbolic_lu(labels.base)
    n_sn = len(R.supernodes(base, max_size=64))
    seen = {labels.base.indices.tobytes()}
    for unit in range(3):
        p = labels(unit)
        assert p.nnz == labels.base.nnz
        seen.add(p.indices.tobytes())
        ref = R.symbolic_lu(p)
        assert ref.lu_nnz == base.lu_nnz
        assert len(R.supernodes(ref, max_size=64)) == n_sn
    assert len(seen) == 4


FOUR_CHIPS = r"""
import json, os, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench.tests.test_correct import small, SEED
from bench import run_cell as rc
if {fault}:
    import repro.runtime.collectives as col

    def shard_zero_only(mesh, axis, shards):
        fp = shards[0]
        fp.seen[:] = True
        return fp

    col.merge_fingerprint_shards = shard_zero_only
cell = small("bbd-20k.analyze")
cell.chips = 4
cell.traffic = rc.load_json(os.path.join(rc.BENCH, "traffic",
                                         "analyze-4chip.json"))
res = rc.run_cell(cell, SEED, 0.3, False, jax.devices())
print(json.dumps(res))
"""


@pytest.mark.parametrize("fault", [False, True],
                         ids=["sound", "exchange_left_out"])
def test_four_chip_analyze(fault):
    """On four virtual CPU devices, with the four-chip mix
    (``traffic/analyze-4chip.json``, kept for the cell a later PR adds):
    the sharded analyze is correct, and with the fingerprint exchange
    between chips left out it is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIPS.format(root=rc.ROOT, src=os.path.join(rc.ROOT, "src"),
                             fault=fault)
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=600)
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is (not fault), res["checks"]
