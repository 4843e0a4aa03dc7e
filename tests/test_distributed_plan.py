"""Multi-device conformance tier for the distributed plan pipeline
(DESIGN.md §11).

The contract under test: ``analyze`` -> ``factorize`` -> ``solve`` through
a sharded mesh produces **bitwise-identical** results at every device
count.  Device count is locked at jax init, so each count {1, 2, 8} runs
in its own subprocess under ``XLA_FLAGS=--xla_force_host_platform_device_
count=N`` over *every* generator in ``sparse/matrices.py``; the parent
process computes the mesh-less reference digests and requires equality of
counts, pattern, panel partition, factors, solutions, and
pickle-roundtrip factors — plus cross-process pickling (a plan analyzed
on 8 devices refactorizes bitwise in the 1-device parent).

The property-based half (hypothesis) pins the fingerprint
merge algebra the tier relies on: per-shard partial fingerprints over any
source sharding fold to exactly the single-shard fingerprints, and the
T2/T3 supernode boundaries are invariant under the shard count.
"""
import hashlib
import json
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.distributed import assign_sources, ownership_mask
from repro.core.gsofa import prepare_graph
from repro.core.multisource import run_multisource
from repro.sparse.csr import csr_from_dense
from repro.supernodes import ColumnFingerprints, detect_from_fingerprints
from repro.supernodes.fingerprint import fingerprints_from_graph

DEVICE_COUNTS = (1, 2, 8)

# every generator in sparse/matrices.py, sized for subprocess turnaround
_GEN_SRC = """
GENERATORS = {
    "grid2d": lambda: grid2d_laplacian(10),
    "grid3d": lambda: grid3d_laplacian(5),
    "circuit": lambda: circuit_like(200, seed=7),
    "economic": lambda: economic_like(192, block=16, seed=2),
    "chemical": lambda: chemical_like(240, stage=16, seed=3),
    "banded": lambda: banded_random(160, band=6, seed=4),
    "banded_full": lambda: banded_full(150, band=5),
    "random": lambda: random_pattern(120, density=0.02, seed=5),
    "bbd": lambda: bordered_block_diagonal(320, block=16, border=32, seed=6),
}
"""

_SCRIPT = r"""
import sys, json, pickle, hashlib
import numpy as np
import jax

n_dev = int(sys.argv[1])
plan_out = sys.argv[2]
assert len(jax.devices()) == n_dev, (len(jax.devices()), n_dev)

from repro.api import LUOptions, analyze
from repro.launch.mesh import make_flat_mesh
from repro.sparse import (
    banded_full, banded_random, bordered_block_diagonal, chemical_like,
    circuit_like, economic_like, grid2d_laplacian, grid3d_laplacian,
    permute_csr, random_pattern, rcm_order,
)
from repro.sparse.numeric import generic_values_csr

__GEN_SRC__

def digest(*arrays):
    h = hashlib.sha256()
    for arr in arrays:
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()

out = {}
for name in sorted(GENERATORS):
    a = GENERATORS[name]()
    a = permute_csr(a, rcm_order(a))
    mesh = make_flat_mesh()
    plan = analyze(a, LUOptions(concurrency=32, supernode_relax=2),
                   mesh=mesh)
    values = generic_values_csr(a)
    factor = plan.factorize(values)
    rng = np.random.default_rng(0)
    b1 = rng.standard_normal(a.n)
    bk = rng.standard_normal((a.n, 3))
    plan2 = pickle.loads(pickle.dumps(plan))
    factor2 = plan2.factorize(values)
    out[name] = {
        "counts": digest(plan.sym.l_counts, plan.sym.u_counts),
        "pattern": digest(plan.pattern.indptr, plan.pattern.rowind),
        "partition": digest(plan.schedule.supernodes,
                            plan.schedule.partition.assignment),
        "factors": digest(*factor.num.store.blocks),
        "solve": digest(factor.solve(b1).x, factor.solve(bk).x),
        "pickle_roundtrip": digest(*factor2.num.store.blocks),
        "n_devices": plan.n_devices,
        "n_panels": plan.n_supernodes,
        "max_level_width": max(len(lv) for lv in plan.schedule.levels),
        "devices_with_panels":
            int(np.unique(plan.placement.device_of_panel).size),
    }
    if name == "circuit":
        with open(plan_out, "wb") as f:
            pickle.dump(plan, f)

# dynamic-runtime sweep (8-device leg only): the work-stealing scheduler
# drives the analyze over the forced devices; every plan is saved so the
# parent can run the elasticity round-trip (place() onto smaller meshes)
if n_dev == 8:
    dyn_plans = {}
    for name in sorted(GENERATORS):
        a = GENERATORS[name]()
        a = permute_csr(a, rcm_order(a))
        dplan = analyze(a, LUOptions(concurrency=32, supernode_relax=2,
                                     runtime="dynamic"))
        out[name]["dyn_counts"] = digest(dplan.sym.l_counts,
                                         dplan.sym.u_counts)
        out[name]["dyn_pattern"] = digest(dplan.pattern.indptr,
                                          dplan.pattern.rowind)
        out[name]["dyn_devices"] = dplan.sym.runtime["n_devices"]
        dyn_plans[name] = dplan
    with open(plan_out + ".dyn", "wb") as f:
        pickle.dump(dyn_plans, f)
print("RESULT " + json.dumps(out))
""".replace("__GEN_SRC__", _GEN_SRC)


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for arr in arrays:
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


def _generators():
    from repro.sparse import (  # noqa: F401 - names used by _GEN_SRC
        banded_full, banded_random, bordered_block_diagonal, chemical_like,
        circuit_like, economic_like, grid2d_laplacian, grid3d_laplacian,
        random_pattern,
    )

    ns = dict(locals())
    exec(_GEN_SRC, ns)          # the literal dict the subprocesses run
    return ns["GENERATORS"]


@pytest.fixture(scope="module")
def reference():
    """Mesh-less single-device digests computed in-process — the anchor
    every forced device count must match bitwise."""
    from repro.api import LUOptions, analyze
    from repro.sparse import permute_csr, rcm_order
    from repro.sparse.numeric import generic_values_csr

    out = {}
    for name, gen in sorted(_generators().items()):
        a = gen()
        a = permute_csr(a, rcm_order(a))
        plan = analyze(a, LUOptions(concurrency=32, supernode_relax=2))
        values = generic_values_csr(a)
        factor = plan.factorize(values)
        rng = np.random.default_rng(0)
        b1 = rng.standard_normal(a.n)
        bk = rng.standard_normal((a.n, 3))
        out[name] = {
            "counts": _digest(plan.sym.l_counts, plan.sym.u_counts),
            "pattern": _digest(plan.pattern.indptr, plan.pattern.rowind),
            "partition": _digest(plan.schedule.supernodes,
                                 plan.schedule.partition.assignment),
            "factors": _digest(*factor.num.store.blocks),
            "solve": _digest(factor.solve(b1).x, factor.solve(bk).x),
        }
    return out


@pytest.fixture(scope="module")
def conformance(tmp_path_factory):
    """One subprocess per forced device count; returns
    {count: (digests, pickled-plan path)}."""
    tmp = tmp_path_factory.mktemp("dplan")
    script = tmp / "conformance.py"
    script.write_text(_SCRIPT)
    results = {}
    for count in DEVICE_COUNTS:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={count}"
        env["JAX_PLATFORMS"] = "cpu"      # never the chip its parent holds
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                         "src")
        plan_path = tmp / f"plan_{count}.pkl"
        proc = subprocess.run(
            [sys.executable, str(script), str(count), str(plan_path)],
            env=env, capture_output=True, text=True, timeout=1200)
        assert proc.returncode == 0, proc.stderr[-4000:]
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("RESULT ")][-1]
        results[count] = (json.loads(line[len("RESULT "):]), plan_path)
    return results


@pytest.mark.parametrize("count", DEVICE_COUNTS)
def test_symbolic_outputs_match_reference(count, conformance, reference):
    """Counts, streamed pattern, and panel partition are identical to the
    mesh-less single-device analysis at every device count."""
    got, _ = conformance[count]
    for name, ref in reference.items():
        for key in ("counts", "pattern", "partition"):
            assert got[name][key] == ref[key], (count, name, key)


@pytest.mark.parametrize("count", DEVICE_COUNTS)
def test_factors_bitwise_identical(count, conformance, reference):
    got, _ = conformance[count]
    for name, ref in reference.items():
        assert got[name]["factors"] == ref["factors"], (count, name)


@pytest.mark.parametrize("count", DEVICE_COUNTS)
def test_solve_bitwise_identical(count, conformance, reference):
    """Single-RHS and multi-RHS solutions (batched level solves + per-
    device segments) are bitwise-identical at every device count."""
    got, _ = conformance[count]
    for name, ref in reference.items():
        assert got[name]["solve"] == ref["solve"], (count, name)


@pytest.mark.parametrize("count", DEVICE_COUNTS)
def test_distributed_plans_pickle(count, conformance, reference):
    """In-subprocess pickle roundtrips refactorize bitwise, and the plan's
    recorded mesh width matches the forced device count."""
    got, _ = conformance[count]
    for name, ref in reference.items():
        assert got[name]["pickle_roundtrip"] == ref["factors"], (count, name)
        assert got[name]["n_devices"] == count


@pytest.mark.parametrize("count", DEVICE_COUNTS)
def test_placement_spreads_panels(count, conformance):
    """Every device the level widths can reach receives panel work: the
    per-level LPT packing fills min(devices, level width) bins, so the
    widest level bounds coverage."""
    got, _ = conformance[count]
    for name, rec in got.items():
        expect = min(count, rec["max_level_width"])
        assert rec["devices_with_panels"] == expect, (count, name)


def test_kernel_placement_beyond_visible_devices_raises():
    """A kernel-backend placement over more devices than this process sees
    is an error, never a silent fall back onto the default device."""
    import jax

    import repro
    from repro.sparse.matrices import bordered_block_diagonal
    from repro.sparse.numeric import generic_values_csr

    a = bordered_block_diagonal(160, block=16, border=8, seed=0)
    want = len(jax.devices()) + 1
    plan = repro.analyze(a, repro.LUOptions(numeric_backend="kernel"))
    plan.place(want)
    assert plan.placement.n_devices == want
    with pytest.raises(ValueError, match="are visible"):
        plan.factorize(generic_values_csr(a))


def test_dynamic_runtime_matches_reference_on_8_devices(conformance,
                                                        reference):
    """``LUOptions(runtime="dynamic")`` under 8 forced devices: the
    work-stealing scheduler's counts and streamed pattern are bitwise the
    mesh-less reference on every generator."""
    got, _ = conformance[8]
    for name, ref in reference.items():
        assert got[name]["dyn_counts"] == ref["counts"], name
        assert got[name]["dyn_pattern"] == ref["pattern"], name
        assert got[name]["dyn_devices"] == 8, name


def test_dynamic_plan_elastic_replacement(conformance, reference):
    """Elasticity round-trip: plans the dynamic runtime analyzed under 8
    forced devices reload in this (1-device) process, ``place()`` onto
    D in {1, 2}, and factorize + solve bitwise-identically to the
    mesh-less reference on every generator."""
    from repro.sparse.numeric import generic_values_csr

    _, plan_path = conformance[8]
    with open(str(plan_path) + ".dyn", "rb") as f:
        dyn_plans = pickle.load(f)
    assert sorted(dyn_plans) == sorted(reference)
    for name, plan in sorted(dyn_plans.items()):
        values = generic_values_csr(plan.a)
        rng = np.random.default_rng(0)
        b1 = rng.standard_normal(plan.n)
        bk = rng.standard_normal((plan.n, 3))
        for d in (1, 2):
            p = pickle.loads(pickle.dumps(plan)).place(d)
            assert p.placement.n_devices <= d
            factor = p.factorize(values)
            assert _digest(*factor.num.store.blocks) == \
                reference[name]["factors"], (name, d)
            assert _digest(factor.solve(b1).x, factor.solve(bk).x) == \
                reference[name]["solve"], (name, d)


def test_cross_process_plan_reuse(conformance, reference):
    """A plan analyzed on 8 forced devices unpickles in this (1-device)
    process and refactorizes bitwise — the refactorization-server pattern
    survives distribution."""
    from repro.sparse.numeric import generic_values_csr

    _, plan_path = conformance[8]
    with open(plan_path, "rb") as f:
        plan = pickle.load(f)
    assert plan.n_devices == 8
    factor = plan.factorize(generic_values_csr(plan.a))
    assert _digest(*factor.num.store.blocks) == \
        reference["circuit"]["factors"]


# ---------------------------------------------------------------------------
# fingerprint merge: sharded partials == single-shard (the algebra the
# distributed analyze path rests on)
# ---------------------------------------------------------------------------

def _sharded_fingerprints(a, n_shards: int):
    """Accumulate per-shard fingerprints exactly like the distributed
    driver (ownership-masked sources), then fold them on the host."""
    graph = prepare_graph(a)
    srcs_mat = assign_sources(a.n, n_shards)
    owned = ownership_mask(srcs_mat)
    shards = []
    for d in range(n_shards):
        fp = ColumnFingerprints(n=a.n)
        srcs = srcs_mat[d][owned[d]]
        if len(srcs):
            run_multisource(graph, concurrency=16, sources=srcs,
                            on_chunk=fp.update)
        shards.append(fp)
    merged = shards[0]
    for shard in shards[1:]:
        merged.merge(shard)
    return merged


def _assert_fingerprints_equal(got: ColumnFingerprints,
                               want: ColumnFingerprints) -> None:
    assert np.array_equal(got.counts, want.counts)
    assert np.array_equal(got.hsum, want.hsum)
    assert np.array_equal(got.hxor, want.hxor)
    assert np.array_equal(got.subdiag, want.subdiag)
    assert got.complete and want.complete


@st.composite
def digraph_shards(draw):
    n = draw(st.integers(min_value=2, max_value=32))
    density = draw(st.floats(min_value=0.03, max_value=0.35))
    seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
    n_shards = draw(st.integers(min_value=1, max_value=6))
    rng = np.random.default_rng(seed)
    dense = rng.random((n, n)) < density
    np.fill_diagonal(dense, True)
    return csr_from_dense(dense), n_shards


@given(digraph_shards())
@settings(max_examples=25, deadline=None)
def test_property_sharded_merge_equals_single_shard(case):
    a, n_shards = case
    single = fingerprints_from_graph(prepare_graph(a), concurrency=16)
    merged = _sharded_fingerprints(a, n_shards)
    _assert_fingerprints_equal(merged, single)


@given(digraph_shards(), st.integers(min_value=0, max_value=3))
@settings(max_examples=25, deadline=None)
def test_property_boundaries_invariant_under_shard_count(case, relax):
    """T2 (relax=0) and T3 (relax>0) supernode ranges do not depend on how
    sources were sharded."""
    a, n_shards = case
    single = fingerprints_from_graph(prepare_graph(a), concurrency=16)
    merged = _sharded_fingerprints(a, n_shards)
    assert np.array_equal(
        detect_from_fingerprints(merged, relax=relax),
        detect_from_fingerprints(single, relax=relax))


# deterministic counterparts: same helper, fixed cases, so the contract is
# exercised even when hypothesis is not installed
@pytest.mark.parametrize("seed,n_shards", [(0, 2), (1, 3), (2, 5), (3, 8)])
def test_sharded_merge_equals_single_shard(seed, n_shards):
    rng = np.random.default_rng(seed)
    n = 40
    dense = rng.random((n, n)) < 0.08
    np.fill_diagonal(dense, True)
    a = csr_from_dense(dense)
    single = fingerprints_from_graph(prepare_graph(a), concurrency=16)
    merged = _sharded_fingerprints(a, n_shards)
    _assert_fingerprints_equal(merged, single)
    for relax in (0, 2):
        assert np.array_equal(
            detect_from_fingerprints(merged, relax=relax),
            detect_from_fingerprints(single, relax=relax))


def test_merge_rejects_overlapping_shards():
    rng = np.random.default_rng(4)
    dense = rng.random((12, 12)) < 0.3
    np.fill_diagonal(dense, True)
    a = csr_from_dense(dense)
    graph = prepare_graph(a)
    fp1 = ColumnFingerprints(n=a.n)
    fp2 = ColumnFingerprints(n=a.n)
    run_multisource(graph, concurrency=8, on_chunk=fp1.update)
    run_multisource(graph, concurrency=8,
                    sources=np.array([0, 1], np.int32), on_chunk=fp2.update)
    with pytest.raises(ValueError, match="overlapping"):
        fp1.merge(fp2)


def test_device_merge_matches_host_merge_on_one_device():
    """merge_fingerprint_shards on a 1-device flat mesh is the identity
    ring — bitwise the host fingerprints (the conformance subprocesses
    cover the >1-device rings)."""
    from repro.launch.mesh import make_flat_mesh
    from repro.runtime.collectives import merge_fingerprint_shards

    rng = np.random.default_rng(5)
    dense = rng.random((20, 20)) < 0.2
    np.fill_diagonal(dense, True)
    a = csr_from_dense(dense)
    fp = fingerprints_from_graph(prepare_graph(a), concurrency=8)
    mesh = make_flat_mesh(1)
    merged = merge_fingerprint_shards(mesh, mesh.axis_names[0], [fp])
    _assert_fingerprints_equal(merged, fp)


# ---------------------------------------------------------------------------
# blocked / autotuned plan cross-process replay (DESIGN.md §16): the chosen
# knobs and merged partition are frozen onto the pickled plan, so factorize
# and solve digests must replay bitwise in a different process
# ---------------------------------------------------------------------------

_BLOCKED_SCRIPT = r"""
import sys, json, pickle, hashlib
import numpy as np

plan_out = sys.argv[1]

from repro.api import LUOptions, analyze
from repro.sparse import (
    banded_full, banded_random, bordered_block_diagonal, chemical_like,
    circuit_like, economic_like, grid2d_laplacian, grid3d_laplacian,
    permute_csr, random_pattern, rcm_order,
)
from repro.sparse.numeric import generic_values_csr

__GEN_SRC__

def digest(*arrays):
    h = hashlib.sha256()
    for arr in arrays:
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()

CASES = {
    "blocked": LUOptions(concurrency=32, supernode_relax=2, blocking=True),
    "autotuned": LUOptions(concurrency=32, autotune=True),
}
out = {}
plans = {}
for name in ("circuit", "bbd", "grid2d"):
    a = GENERATORS[name]()
    a = permute_csr(a, rcm_order(a))
    values = generic_values_csr(a)
    rng = np.random.default_rng(0)
    b1 = rng.standard_normal(a.n)
    bk = rng.standard_normal((a.n, 3))
    for case, opts in CASES.items():
        plan = analyze(a, opts)
        factor = plan.factorize(values)
        out[f"{name}/{case}"] = {
            "factors": digest(*factor.num.store.blocks),
            "solve": digest(factor.solve(b1).x, factor.solve(bk).x),
            "n_panels": plan.n_supernodes,
            "chosen": (plan.tuned.chosen if plan.tuned is not None
                       else None),
        }
        plans[f"{name}/{case}"] = plan
with open(plan_out, "wb") as f:
    pickle.dump(plans, f)
print("RESULT " + json.dumps(out))
""".replace("__GEN_SRC__", _GEN_SRC)


@pytest.fixture(scope="module")
def blocked_conformance(tmp_path_factory):
    """One subprocess that analyzes with blocking / autotune on, digests
    its factors + solves, and pickles every plan for the parent."""
    tmp = tmp_path_factory.mktemp("blocked_plan")
    script = tmp / "blocked.py"
    script.write_text(_BLOCKED_SCRIPT)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"      # never the chip its parent holds
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    plan_path = tmp / "plans.pkl"
    proc = subprocess.run(
        [sys.executable, str(script), str(plan_path)],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):]), plan_path


def test_blocked_plans_replay_bitwise_across_processes(blocked_conformance):
    """Every pickled blocked/autotuned plan factorizes and solves in THIS
    process to exactly the digests the analyzing process recorded — the
    frozen partition + knobs leave nothing host- or process-dependent."""
    from repro.sparse.numeric import generic_values_csr

    digests, plan_path = blocked_conformance
    with open(plan_path, "rb") as f:
        plans = pickle.load(f)
    assert sorted(plans) == sorted(digests)
    for key, plan in sorted(plans.items()):
        values = generic_values_csr(plan.a)
        factor = plan.factorize(values)
        assert _digest(*factor.num.store.blocks) == \
            digests[key]["factors"], key
        rng = np.random.default_rng(0)
        b1 = rng.standard_normal(plan.n)
        bk = rng.standard_normal((plan.n, 3))
        assert _digest(factor.solve(b1).x, factor.solve(bk).x) == \
            digests[key]["solve"], key
        assert plan.n_supernodes == digests[key]["n_panels"], key


def test_autotuned_plans_freeze_chosen_knobs(blocked_conformance):
    """The subprocess's TuneReport survives pickling with the chosen knob
    values applied to the plan's options (replay never re-tunes)."""
    digests, plan_path = blocked_conformance
    with open(plan_path, "rb") as f:
        plans = pickle.load(f)
    for key, plan in sorted(plans.items()):
        if not key.endswith("/autotuned"):
            assert plan.tuned is None
            continue
        assert plan.tuned is not None
        assert plan.tuned.chosen == digests[key]["chosen"], key
        assert plan.options.blocking is True
        assert plan.options.supernode_relax == \
            plan.tuned.chosen["supernode_relax"]
        # replanning the loaded plan with its own (frozen) options
        # reproduces the same partition without re-running autotune
        from repro.api import replan

        re = replan(plan, plan.options.replace(autotune=False))
        assert np.array_equal(re.schedule.supernodes,
                              plan.schedule.supernodes), key


def test_ownership_mask_covers_every_source_once():
    for n, d in ((10, 4), (17, 8), (3, 8), (64, 3)):
        mat = assign_sources(n, d)
        owned = ownership_mask(mat)
        srcs = mat[owned]
        assert len(srcs) == n
        assert np.array_equal(np.sort(srcs), np.arange(n))
