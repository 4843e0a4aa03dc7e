"""Correctness of the symbolic-factorization core against independent oracles.

Chain of evidence:
  elimination_fill (definition of fill)  ==  minimax_fill (Theorem 1 semiring)
  ==  fill2 (paper Fig 4a)  ==  GSoFa fixpoint (paper Fig 4b, all backends)
  ==  multi-source / arena / bubble variants.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.fill2 import fill2_dense
from repro.core.gsofa import prepare_graph, dense_pattern, gsofa_batch
from repro.core.multisource import run_multisource
from repro.core.symbolic import symbolic_factorize
from repro.core.theory import elimination_fill, minimax_fill, fill_ratio
from repro.sparse import (
    banded_random, chemical_like, circuit_like, economic_like, grid2d_laplacian,
    grid3d_laplacian, random_pattern, rcm_order, permute_csr,
)
from repro.sparse.csr import csr_from_dense

MATS = {
    "grid2d": lambda: grid2d_laplacian(7),
    "grid3d": lambda: grid3d_laplacian(4),
    "circuit": lambda: circuit_like(120, seed=1),
    "economic": lambda: economic_like(96, block=12, seed=2),
    "chemical": lambda: chemical_like(128, stage=16, seed=3),
    "banded": lambda: banded_random(100, band=6, seed=4),
    "random": lambda: random_pattern(80, density=0.05, seed=5),
    "random_sym": lambda: random_pattern(64, density=0.05, symmetric=True, seed=6),
}


def _ref_counts(a):
    e = elimination_fill(a)
    np.fill_diagonal(e, False)
    ids = np.arange(a.n)
    return ((e & (ids[None, :] < ids[:, None])).sum(1),
            (e & (ids[None, :] > ids[:, None])).sum(1))


@pytest.mark.parametrize("name", sorted(MATS))
def test_oracles_agree(name):
    a = MATS[name]()
    assert np.array_equal(elimination_fill(a), minimax_fill(a)), \
        "Theorem-1 minimax closure must equal elimination fill"


@pytest.mark.parametrize("name", sorted(MATS))
def test_fill2_matches_oracle(name):
    a = MATS[name]()
    assert np.array_equal(fill2_dense(a), elimination_fill(a))


@pytest.mark.parametrize("name", sorted(MATS))
@pytest.mark.parametrize("backend", ["ell", "dense", "kernel"])
def test_gsofa_matches_oracle(name, backend):
    a = MATS[name]()
    dense_block = 128 if backend in ("dense", "kernel") else None
    g = prepare_graph(a, dense_block=dense_block)
    got = dense_pattern(g, backend=backend, batch=48)
    assert np.array_equal(got, elimination_fill(a))


@pytest.mark.parametrize("kwargs", [
    dict(combined=True, use_arena=True),
    dict(combined=True, use_arena=False),
    dict(combined=False, use_arena=False),
    dict(combined=True, bubble=True),
])
def test_multisource_variants(kwargs):
    a = circuit_like(150, seed=7)
    l_ref, u_ref = _ref_counts(a)
    r = run_multisource(prepare_graph(a), concurrency=48, **kwargs)
    assert np.array_equal(r.l_counts, l_ref)
    assert np.array_equal(r.u_counts, u_ref)


def test_arena_reuses_windows_without_reinit():
    a = grid2d_laplacian(12)  # 144 vertices -> 3 chunks at #C=64
    r = run_multisource(prepare_graph(a), concurrency=64, use_arena=True)
    assert r.windows >= 3
    assert r.reinits == 1, "window trick must avoid per-chunk re-initialization"


def test_combined_traversal_reduces_supersteps():
    a = circuit_like(200, seed=8)
    g = prepare_graph(a)
    combined = run_multisource(g, concurrency=64, combined=True)
    separate = run_multisource(g, concurrency=64, combined=False)
    assert np.array_equal(combined.l_counts, separate.l_counts)
    assert combined.supersteps < separate.supersteps / 4


def test_public_api_counts_and_fill_ratio():
    a = economic_like(128, block=16, seed=9)
    l_ref, u_ref = _ref_counts(a)
    r = symbolic_factorize(a, concurrency=64)
    assert np.array_equal(r.l_counts, l_ref)
    assert np.array_equal(r.u_counts, u_ref)
    assert r.fill_ratio == pytest.approx(
        fill_ratio(a, elimination_fill(a)) * a.nnz / a.nnz, rel=1e-6)


def test_memory_budget_reduces_concurrency():
    a = circuit_like(400, seed=10)
    g = prepare_graph(a)
    small = symbolic_factorize(a, graph=g, concurrency=256, budget_bytes=1_500_000)
    big = symbolic_factorize(a, graph=g, concurrency=256)
    assert small.concurrency < big.concurrency
    assert np.array_equal(small.l_counts, big.l_counts)


def test_workload_grows_with_source_id():
    """Paper Fig 3: average frontier workload rises with the source id."""
    a = grid2d_laplacian(14)
    r = run_multisource(prepare_graph(a), concurrency=64)
    n = a.n
    lo = r.edge_checks[: n // 4].mean()
    hi = r.edge_checks[3 * n // 4:].mean()
    assert hi > 2 * lo


def test_rcm_reordering_reduces_fill():
    a = random_pattern(120, density=0.03, symmetric=True, seed=11)
    base = elimination_fill(a).sum()
    perm = rcm_order(a)
    ra = permute_csr(a, perm)
    reordered = elimination_fill(ra).sum()
    assert reordered < base  # RCM should not hurt on a random symmetric pattern
    # and GSoFa agrees on the reordered matrix too
    assert np.array_equal(dense_pattern(prepare_graph(ra)), elimination_fill(ra))


# ---------------------------------------------------------------------------
# property-based: random digraphs, invariants of the label fixpoint
# ---------------------------------------------------------------------------

@st.composite
def digraphs(draw):
    n = draw(st.integers(min_value=2, max_value=28))
    density = draw(st.floats(min_value=0.02, max_value=0.35))
    seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
    rng = np.random.default_rng(seed)
    dense = rng.random((n, n)) < density
    np.fill_diagonal(dense, True)
    return csr_from_dense(dense)


@given(digraphs())
@settings(max_examples=60, deadline=None)
def test_property_gsofa_equals_elimination(a):
    g = prepare_graph(a)
    assert np.array_equal(dense_pattern(g, batch=32), elimination_fill(a))


@given(digraphs())
@settings(max_examples=40, deadline=None)
def test_property_fill_superset_of_A_and_monotone(a):
    """Invariants: L+U contains A; labels are lower bounds that only decrease."""
    g = prepare_graph(a)
    pat = dense_pattern(g, batch=32)
    assert np.all(pat | ~a.to_dense() == pat | ~a.to_dense())  # well-formed
    assert np.all((a.to_dense() & ~np.eye(a.n, dtype=bool)) <= pat)
    # monotonicity: running extra supersteps never changes the converged labels
    srcs = np.arange(a.n, dtype=np.int32)
    r1 = gsofa_batch(g, srcs)
    r2 = gsofa_batch(g, srcs, max_iters=4 * (a.n + 2))
    assert np.array_equal(np.asarray(r1.labels), np.asarray(r2.labels))


@given(digraphs(), st.integers(min_value=1, max_value=5))
@settings(max_examples=30, deadline=None)
def test_property_chunking_invariant(a, chunks):
    """Counts are independent of how sources are chunked (#C)."""
    l_ref, u_ref = _ref_counts(a)
    c = max(1, a.n // chunks)
    r = run_multisource(prepare_graph(a), concurrency=c)
    assert np.array_equal(r.l_counts, l_ref)
    assert np.array_equal(r.u_counts, u_ref)


def test_supernode_detection():
    """Paper §V: supernode detection as a post-pass (grid matrices have
    nontrivial supernodes after fill)."""
    from repro.core.gsofa import dense_pattern, prepare_graph
    from repro.core.symbolic import detect_supernodes
    from repro.sparse import grid2d_laplacian, permute_csr, rcm_order

    a = grid2d_laplacian(12)
    a = permute_csr(a, rcm_order(a))
    pattern = dense_pattern(prepare_graph(a))
    sn = detect_supernodes(pattern)
    # ranges are a partition of the columns
    assert sn[0, 0] == 0 and sn[-1, 1] == a.n
    assert (sn[1:, 0] == sn[:-1, 1]).all()
    sizes = sn[:, 1] - sn[:, 0]
    assert (sizes >= 1).all()
    # dense trailing blocks of a filled grid produce multi-column supernodes
    assert sizes.max() >= 2
    # inside a supernode every column has identical below-block structure
    s, e = sn[sizes.argmax()]
    for j in range(s + 1, e):
        assert (pattern[e:, j] == pattern[e:, s]).all()
