"""Plan gather maps: the rank-table builder against the per-panel oracle.

``build_gather_maps`` picks one of two builders by cost — one
``local_rows`` search per (panel, ancestor), or one dense (panel, row)
rank table read by a gather per panel — and both must give exactly the
maps ``build_panel_maps`` gives, panel by panel, dtypes included.  With
tracing on, the plan records which builder ran and how many map entries
it built.
"""
import numpy as np
import pytest

from repro.api import LUOptions, analyze
from repro.numeric.schedule import (
    _gather_maps_by_search, _gather_maps_by_table, build_gather_maps,
    build_panel_maps, gather_map_entries,
)
from repro.obs import metrics as om
from repro.obs import trace as ot
from repro.sparse import (
    bordered_block_diagonal, grid3d_laplacian, permute_csr, random_pattern,
    rcm_order,
)
from repro.sparse.numeric import generic_values_csr


def _grid():
    a = grid3d_laplacian(6)
    return permute_csr(a, rcm_order(a))


# pattern -> (matrix, builder build_gather_maps picks for it)
PATTERNS = {
    "grid3d_rcm": (_grid, "table"),
    "bbd": (lambda: bordered_block_diagonal(256, block=16, border=16,
                                            seed=6), "search"),
    "random_nonsym": (lambda: random_pattern(160, density=0.02, seed=5),
                      "table"),
}
BUILDERS = {"search": _gather_maps_by_search, "table": _gather_maps_by_table}
OPTS = LUOptions(concurrency=64, supernode_relax=0, supernode_max_size=64)


@pytest.fixture(autouse=True)
def _clean_obs_state():
    ot.disable()
    om.registry().reset()
    yield
    ot.disable()
    om.registry().reset()


@pytest.fixture(scope="module")
def plans():
    return {name: analyze(make(), OPTS)
            for name, (make, _) in PATTERNS.items()}


def _assert_same_array(got, want):
    assert got.dtype == want.dtype
    assert np.array_equal(got, want)


def _assert_same_maps(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        if w is None:
            assert g is None
            continue
        for field in ("anc_rows", "offs", "idx_j", "hit_j"):
            _assert_same_array(getattr(g, field), getattr(w, field))
        for field in ("strip_maps", "below_maps"):
            assert len(getattr(g, field)) == len(getattr(w, field))
            for (gi, gh), (wi, wh) in zip(getattr(g, field),
                                          getattr(w, field)):
                _assert_same_array(gi, wi)
                _assert_same_array(gh, wh)


def _summed_lengths(maps):
    return sum(len(idx) for m in maps if m is not None
               for idx, _ in m.strip_maps + m.below_maps)


@pytest.mark.parametrize("builder", sorted(BUILDERS))
@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_builder_matches_per_panel_oracle(name, builder, plans):
    plan = plans[name]
    store, schedule = plan.store_template, plan.schedule
    oracle = [build_panel_maps(store, schedule, j)
              for j in range(schedule.n_panels)]
    assert any(m is not None for m in oracle)
    _assert_same_maps(BUILDERS[builder](store, schedule), oracle)


@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_plan_maps_take_the_cheaper_builder(name, plans):
    plan = plans[name]
    store, schedule = plan.store_template, plan.schedule
    entries = gather_map_entries(store, schedule)
    assert entries == _summed_lengths(plan.gather_maps)
    table = schedule.n_panels * store.n <= entries
    assert table == (PATTERNS[name][1] == "table")
    _assert_same_maps(build_gather_maps(store, schedule), plan.gather_maps)


@pytest.mark.parametrize("name", ["grid3d_rcm", "bbd"])
def test_analyze_counts_map_entries_and_builder(name):
    make, builder = PATTERNS[name]
    a = make()
    plan = analyze(a, OPTS.replace(trace=True))
    reg = om.registry()
    assert reg.get("plan.gather_map_table") == int(builder == "table")
    assert reg.get("plan.gather_map_entries") == _summed_lengths(
        plan.gather_maps)
    if builder == "table":
        values = generic_values_csr(a)
        b = np.random.default_rng(3).standard_normal(a.n)
        x = plan.factorize(values).solve(b).x
        rows = np.repeat(np.arange(a.n), np.diff(a.indptr))
        ax = np.bincount(rows, weights=values * x[a.indices], minlength=a.n)
        assert np.linalg.norm(b - ax) <= 1e-10 * np.linalg.norm(b)
