"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops


# ---------------------------------------------------------------------------
# minmax relaxation kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,u,v", [
    (1, 8, 16), (4, 50, 70), (8, 128, 256), (3, 200, 130),
    (16, 256, 512), (9, 131, 257), (2, 1, 1),
])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_minmax_relax_shapes(s, u, v, dtype):
    rng = np.random.default_rng(s * 1000 + u + v)
    if dtype == jnp.int32:
        prop = rng.integers(-1, u + 1, size=(s, u)).astype(np.int32)
        inf = np.iinfo(np.int32).max
    else:
        prop = rng.standard_normal((s, u)).astype(np.float32)
        inf = np.inf
    prop[rng.random((s, u)) < 0.3] = inf
    adj = (rng.random((u, v)) < 0.15).astype(np.uint8)
    out = ops.minmax_relax(jnp.asarray(prop), jnp.asarray(adj))
    ref = ops.minmax_relax_ref(jnp.asarray(prop), jnp.asarray(adj))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("blocks", [(8, 8, 128), (8, 16, 128), (16, 128, 256)])
def test_minmax_relax_block_shape_invariance(blocks):
    bs, bu, bv = blocks
    rng = np.random.default_rng(0)
    prop = rng.integers(0, 100, size=(10, 70)).astype(np.int32)
    adj = (rng.random((70, 90)) < 0.2).astype(np.uint8)
    out = ops.minmax_relax(jnp.asarray(prop), jnp.asarray(adj),
                           block_s=bs, block_u=bu, block_v=bv)
    ref = ops.minmax_relax_ref(jnp.asarray(prop), jnp.asarray(adj))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_minmax_relax_empty_adjacency_gives_inf():
    prop = jnp.zeros((4, 32), jnp.int32)
    adj = jnp.zeros((32, 64), jnp.uint8)
    out = ops.minmax_relax(prop, adj)
    assert int(out.min()) == np.iinfo(np.int32).max


@given(st.integers(1, 12), st.integers(1, 64), st.integers(1, 64),
       st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_minmax_relax_property(s, u, v, seed):
    rng = np.random.default_rng(seed)
    prop = rng.integers(-1, 2 * u, size=(s, u)).astype(np.int32)
    adj = (rng.random((u, v)) < rng.uniform(0, 0.5)).astype(np.uint8)
    out = ops.minmax_relax(jnp.asarray(prop), jnp.asarray(adj))
    ref = ops.minmax_relax_ref(jnp.asarray(prop), jnp.asarray(adj))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# supernode fingerprint kernel
# ---------------------------------------------------------------------------

def _fp_inputs(s, v, seed):
    rng = np.random.default_rng(seed)
    rel = rng.integers(-1, v + 2, size=(s, v)).astype(np.int32)
    src = rng.integers(0, v, size=s).astype(np.int32)
    m1 = rng.integers(0, 2**32, size=s, dtype=np.uint64).astype(np.uint32)
    m2 = rng.integers(0, 2**32, size=s, dtype=np.uint64).astype(np.uint32)
    valid = (rng.random(s) < 0.8).astype(np.int32)
    return tuple(jnp.asarray(x) for x in
                 (rel, src, m1.view(np.int32), m2.view(np.int32), valid))


@pytest.mark.parametrize("s,v", [
    (1, 1), (5, 100), (8, 512), (13, 300), (16, 1024), (33, 700),
])
def test_supernode_fp_shapes(s, v):
    args = _fp_inputs(s, v, seed=s * 101 + v)
    out = ops.column_fingerprints(*args)
    ref = ops.column_fingerprints_ref(*args)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("block_v", [128, 256, 512])
def test_supernode_fp_block_shape_invariance(block_v):
    args = _fp_inputs(20, 600, seed=0)
    out = ops.column_fingerprints(*args, block_v=block_v)
    ref = ops.column_fingerprints_ref(*args)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_supernode_fp_invalid_rows_contribute_nothing():
    rel, src, m1, m2, _ = _fp_inputs(9, 200, seed=3)
    none = ops.column_fingerprints(rel, src, m1, m2,
                                   jnp.zeros(9, jnp.int32))
    assert int(jnp.abs(none).max()) == 0


@given(st.integers(1, 24), st.integers(1, 200), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_supernode_fp_property(s, v, seed):
    args = _fp_inputs(s, v, seed)
    out = ops.column_fingerprints(*args)
    ref = ops.column_fingerprints_ref(*args)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# supernodal panel-update kernel
# ---------------------------------------------------------------------------

def _pu_inputs(m, n, k, seed):
    rng = np.random.default_rng(seed)
    acc = rng.standard_normal((m, n)).astype(np.float32)
    lp = rng.standard_normal((m, k)).astype(np.float32)
    up = rng.standard_normal((k, n)).astype(np.float32)
    return tuple(jnp.asarray(x) for x in (acc, lp, up))


@pytest.mark.parametrize("m,n,k", [
    (1, 1, 1), (5, 100, 7), (8, 128, 128), (64, 64, 64), (130, 260, 70),
    (200, 300, 150), (17, 129, 33),
])
def test_panel_update_shapes(m, n, k):
    args = _pu_inputs(m, n, k, seed=m * 7 + n + k)
    out = ops.panel_update(*args)
    ref = ops.panel_update_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("blocks", [(8, 128, 128), (16, 128, 256),
                                    (128, 128, 128)])
def test_panel_update_block_shape_invariance(blocks):
    bm, bn, bk = blocks
    args = _pu_inputs(70, 200, 90, seed=0)
    out = ops.panel_update(*args, block_m=bm, block_n=bn, block_k=bk)
    ref = ops.panel_update_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_panel_update_empty_contraction_is_identity():
    acc = jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4))
    lp = jnp.zeros((3, 0), jnp.float32)
    up = jnp.zeros((0, 4), jnp.float32)
    np.testing.assert_array_equal(np.asarray(ops.panel_update(acc, lp, up)),
                                  np.asarray(acc))


def test_panel_update_zero_l_keeps_acc():
    acc, lp, up = _pu_inputs(24, 140, 40, seed=2)
    out = ops.panel_update(acc, jnp.zeros_like(lp), up)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(acc))


@given(st.integers(1, 40), st.integers(1, 80), st.integers(1, 48),
       st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_panel_update_property(m, n, k, seed):
    args = _pu_inputs(m, n, k, seed)
    out = ops.panel_update(*args)
    ref = ops.panel_update_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,s,t,d", [
    (1, 1, 8, 8, 16), (1, 2, 16, 16, 32), (2, 2, 64, 64, 64),
    (1, 1, 8, 32, 16),      # decode-style: queries are the last 8 of 32
    (1, 1, 1, 40, 64),      # single-token decode
    (1, 2, 24, 24, 48),     # non-power-of-two d
])
def test_flash_attention_shapes(b, h, s, t, d):
    rng = np.random.default_rng(b + h + s + t + d)
    q = rng.standard_normal((b, h, s, d)).astype(np.float32)
    k = rng.standard_normal((b, h, t, d)).astype(np.float32)
    v = rng.standard_normal((b, h, t, d)).astype(np.float32)
    out = ops.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=True, block_q=8, block_k=16)
    ref = ops.flash_attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                  causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, 2, 32, 64)), dtype)
    k = jnp.asarray(rng.standard_normal((1, 2, 32, 64)), dtype)
    v = jnp.asarray(rng.standard_normal((1, 2, 32, 64)), dtype)
    out = ops.flash_attention(q, k, v, block_q=8, block_k=16)
    ref = ops.flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)
    assert out.dtype == dtype


def test_flash_attention_noncausal():
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((1, 1, 16, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 48, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 48, 32)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, block_q=8, block_k=16)
    ref = ops.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
