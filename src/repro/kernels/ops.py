"""Jit'd public wrappers around the Pallas kernels.

Each wrapper pads to block multiples, dispatches to the kernel (interpret mode
everywhere except real TPU), and slices the result back.  ``ref.py`` holds the
pure-jnp oracles the tests compare against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref
from repro.kernels.gsofa_relax import minmax_relax_pallas
from repro.kernels.panel_update import panel_update_pallas
from repro.kernels.supernode_fp import supernode_fp_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.obs import trace as _ot


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _host_padded(x, shape) -> jax.Array:
    """float32 ``x`` zero-padded up to ``shape``, padded on the host.

    The panel GEMMs see hundreds of distinct logical shapes per plan but only
    a handful of padded ones; padding and casting with numpy before the
    transfer keeps the device compiling one program per padded shape, not a
    pad, cast and slice program per logical shape."""
    x = np.asarray(x, np.float32)
    return _ot.put(np.pad(x, [(0, t - d) for d, t in zip(x.shape, shape)]),
                   "panel operand")


def padded_gemm_shape(m, k, n, *, block_m: int = 128, block_n: int = 128,
                      block_k: int = 128):
    """Padded ``(M, K, N)`` that ``panel_update`` actually dispatches for a
    logical ``m x k @ k x n`` update.

    The block-sizing :func:`panel_update` pads with (sublane multiples of 8
    on M, lane multiples of 128 on K/N), shared so cost models can charge the
    explicit-zero MXU work instead of the logical shape.  Accepts scalars or
    numpy arrays (vectorised over candidate partitions); zero-sized operands
    stay zero since those dispatches are skipped entirely.
    """
    m_ = np.asarray(m, dtype=np.int64)
    k_ = np.asarray(k, dtype=np.int64)
    n_ = np.asarray(n, dtype=np.int64)
    bm = np.minimum(block_m, np.maximum(8, ((m_ + 7) // 8) * 8))
    bk = np.minimum(block_k, np.maximum(128, ((k_ + 127) // 128) * 128))
    bn = np.minimum(block_n, np.maximum(128, ((n_ + 127) // 128) * 128))
    mp = np.where(m_ > 0, ((m_ + bm - 1) // np.maximum(bm, 1)) * bm, 0)
    kp = np.where(k_ > 0, ((k_ + bk - 1) // np.maximum(bk, 1)) * bk, 0)
    np_ = np.where(n_ > 0, ((n_ + bn - 1) // np.maximum(bn, 1)) * bn, 0)
    dead = (m_ == 0) | (k_ == 0) | (n_ == 0)
    mp, kp, np_ = (np.where(dead, 0, x) for x in (mp, kp, np_))
    if np.isscalar(m) and np.isscalar(k) and np.isscalar(n):
        return int(mp), int(kp), int(np_)
    return mp, kp, np_


def minmax_relax(prop: jax.Array, adj: jax.Array, *, block_s: int = 8,
                 block_u: int = 128, block_v: int = 256,
                 interpret: bool | None = None) -> jax.Array:
    """Bottleneck-semiring relaxation; see gsofa_relax.py.  Pads + dispatches."""
    if interpret is None:
        interpret = not _on_tpu()
    s, u = prop.shape
    _, v = adj.shape
    inf = _ref._inf(prop.dtype)
    block_u = min(block_u, max(8, ((u + 7) // 8) * 8))
    block_v = min(block_v, max(128, ((v + 127) // 128) * 128))
    prop_p = _pad_to(_pad_to(prop, 0, block_s, inf), 1, block_u, inf)
    adj_p = _pad_to(_pad_to(adj, 0, block_u, 0), 1, block_v, 0)
    out = minmax_relax_pallas(prop_p, adj_p, block_s=block_s, block_u=block_u,
                              block_v=block_v, interpret=interpret)
    return out[:s, :v]


def minmax_relax_ref(prop: jax.Array, adj: jax.Array) -> jax.Array:
    return _ref.minmax_relax_ref(prop, adj)


def column_fingerprints(rel: jax.Array, src: jax.Array, m1: jax.Array,
                        m2: jax.Array, valid: jax.Array, *, block_s: int = 8,
                        block_v: int = 512,
                        interpret: bool | None = None) -> jax.Array:
    """(3, V) per-column supernode fingerprints; see supernode_fp.py.

    Pads the source axis to ``block_s`` (invalid rows) and the vertex axis to
    ``block_v`` (labels clamped high so padded columns read as empty), packs
    the per-source lanes into the (S, 8) meta layout, and slices back.
    """
    if interpret is None:
        interpret = not _on_tpu()
    s, v = rel.shape
    block_v = min(block_v, max(128, ((v + 127) // 128) * 128))
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    rel_p = _pad_to(_pad_to(rel, 0, block_s, big), 1, block_v, big)
    sp = rel_p.shape[0]
    lanes = jnp.stack([x.astype(jnp.int32) for x in (src, m1, m2, valid)],
                      axis=1)                               # (s, 4)
    meta = jnp.pad(lanes, ((0, sp - s), (0, 4)))            # (sp, 8)
    out = supernode_fp_pallas(rel_p, meta, block_s=block_s, block_v=block_v,
                              interpret=interpret)
    return out[:3, :v]


def column_fingerprints_ref(rel: jax.Array, src: jax.Array, m1: jax.Array,
                            m2: jax.Array, valid: jax.Array) -> jax.Array:
    return _ref.supernode_fp_ref(rel, src, m1, m2, valid)


def panel_update(acc: np.ndarray, l_panel: np.ndarray, u_panel: np.ndarray,
                 *, block_m: int = 128, block_n: int = 128, block_k: int = 128,
                 interpret: bool | None = None) -> np.ndarray:
    """(M, N) supernodal panel update ``acc - l_panel @ u_panel``; see
    panel_update.py.  Pads all three operands with zeros on the host (zero
    products leave the padded region inert) and slices back.  float32 — the
    numeric layer (repro.numeric) keeps its float64 path on numpy and
    routes the heavy GEMM here on TPU."""
    if interpret is None:
        interpret = not _on_tpu()
    acc = np.asarray(acc, np.float32)
    m, n = acc.shape
    k = l_panel.shape[1]
    if m == 0 or n == 0 or k == 0:
        return acc
    mp, kp, np_ = padded_gemm_shape(m, k, n, block_m=block_m,
                                    block_n=block_n, block_k=block_k)
    # a dim shorter than its block was padded to a block of its own size
    out = panel_update_pallas(_host_padded(acc, (mp, np_)),
                              _host_padded(l_panel, (mp, kp)),
                              _host_padded(u_panel, (kp, np_)),
                              block_m=min(block_m, mp),
                              block_n=min(block_n, np_),
                              block_k=min(block_k, kp), interpret=interpret)
    return _ot.fetch(out, "panel update")[:m, :n]


def panel_update_ref(acc, l_panel, u_panel):
    return _ref.panel_update_ref(jnp.asarray(acc, jnp.float32),
                                 jnp.asarray(l_panel, jnp.float32),
                                 jnp.asarray(u_panel, jnp.float32))


def panel_update_batched(acc: np.ndarray, l_panel: np.ndarray,
                         u_panel: np.ndarray, *, block_m: int = 128,
                         block_n: int = 128, block_k: int = 128,
                         interpret: bool | None = None) -> np.ndarray:
    """(B, M, N) stacked supernodal panel updates in ONE kernel launch; see
    ``panel_update_batched_pallas``.  Pads the trailing dims with the exact
    block sizes the per-panel ``panel_update`` wrapper would pick for
    (M, N, K), so every slice is bitwise-identical to its own per-panel
    dispatch — the batched segment sweep's conformance contract."""
    from repro.kernels.panel_update import panel_update_batched_pallas

    if interpret is None:
        interpret = not _on_tpu()
    acc = np.asarray(acc, np.float32)
    b, m, n = acc.shape
    k = l_panel.shape[2]
    if b == 0 or m == 0 or n == 0 or k == 0:
        return acc
    mp, kp, np_ = padded_gemm_shape(m, k, n, block_m=block_m,
                                    block_n=block_n, block_k=block_k)
    out = panel_update_batched_pallas(_host_padded(acc, (b, mp, np_)),
                                      _host_padded(l_panel, (b, mp, kp)),
                                      _host_padded(u_panel, (b, kp, np_)),
                                      block_m=min(block_m, mp),
                                      block_n=min(block_n, np_),
                                      block_k=min(block_k, kp),
                                      interpret=interpret)
    return _ot.fetch(out, "panel update")[:, :m, :n]


def panel_update_systems(acc, l_panel, u_panel, *,
                         interpret: bool | None = None) -> np.ndarray:
    """Stacked panel updates with arbitrary leading batch axes — the
    many-matrix tier's GEMM entry point (DESIGN.md §14).

    ``acc`` is (..., M, N), ``l_panel`` (..., M, K), ``u_panel`` (..., K, N);
    every leading axis (systems, same-shape panel groups, or both) is
    flattened into the one stacked-batch axis ``panel_update_batched``
    already launches over, so a (B_systems, M, N) system batch and a
    (B_systems, G, M, N) system-x-group batch reuse the same single Pallas
    dispatch — and every slice stays bitwise-identical to its own
    per-panel ``panel_update`` call (the vmap per-slice grid parity that
    the within-plan segment batching relies on)."""
    acc, l_panel, u_panel = (np.asarray(x) for x in (acc, l_panel, u_panel))
    lead = acc.shape[:-2]
    m, n = acc.shape[-2:]
    k = l_panel.shape[-1]
    out = panel_update_batched(acc.reshape((-1, m, n)),
                               l_panel.reshape((-1, m, k)),
                               u_panel.reshape((-1, k, n)),
                               interpret=interpret)
    return out.reshape(lead + (m, n))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """Blocked online-softmax attention; see flash_attention.py."""
    if interpret is None:
        interpret = not _on_tpu()
    return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)


def flash_attention_ref(q, k, v, *, causal: bool = True, scale: float | None = None):
    return _ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)


def mamba_scan(x, dt, b_t, c_t, a, d_skip, *, block_d: int = 512,
               block_t: int = 128, interpret: bool | None = None):
    """VMEM-resident selective scan; see ssm_scan.py.  Pads L/di to blocks."""
    from repro.kernels.ssm_scan import mamba_scan_pallas
    if interpret is None:
        interpret = not _on_tpu()
    bsz, l, di = x.shape
    block_d = min(block_d, di)
    block_t = min(block_t, max(8, l))
    def padded(t, axis, mult):
        return _pad_to(t, axis, mult, 0.0)
    xp = padded(padded(x, 1, block_t), 2, block_d)
    dtp = padded(padded(dt, 1, block_t), 2, block_d)
    btp = padded(b_t, 1, block_t)
    ctp = padded(c_t, 1, block_t)
    ap = _pad_to(a, 0, block_d, -1.0)
    dp = _pad_to(d_skip, 0, block_d, 0.0)
    y = mamba_scan_pallas(xp, dtp, btp, ctp, ap, dp, block_d=block_d,
                          block_t=block_t, interpret=interpret)
    return y[:, :l, :di]


def mamba_scan_ref(x, dt, b_t, c_t, a, d_skip):
    return _ref.mamba_scan_ref(x, dt, b_t, c_t, a, d_skip)


def rwkv6_scan(r, k, v, w, u, *, block_t: int = 128,
               interpret: bool | None = None):
    """VMEM-resident rwkv6 time-mix recurrence; see ssm_scan.py."""
    from repro.kernels.ssm_scan import rwkv6_scan_pallas
    if interpret is None:
        interpret = not _on_tpu()
    bh, l, kk = r.shape
    block_t = min(block_t, max(8, l))
    rp, kp, vp = (_pad_to(t, 1, block_t, 0.0) for t in (r, k, v))
    wp = _pad_to(w, 1, block_t, 1.0)
    o = rwkv6_scan_pallas(rp, kp, vp, wp, u, block_t=block_t,
                          interpret=interpret)
    return o[:, :l]


def rwkv6_scan_ref(r, k, v, w, u):
    return _ref.rwkv6_scan_ref(r, k, v, w, u)
