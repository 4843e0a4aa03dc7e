"""Pallas TPU kernel: per-column supernode fingerprints from GSoFa labels.

Supernode detection (DESIGN.md §3) needs, for every column ``j``, a summary of
the strictly-below-diagonal structure of L's column ``j``:

    cnt[j]  = |{ i > j : filled(i, j) }|
    hsum[j] = sum  over that set of mix1(i)   (wrapping int32)
    hxor[j] = xor  over that set of mix2(i)

Row ``i`` of the filled pattern is exactly the converged label row of source
``i`` (``filled(i, v) <=> maxId[v] < v``), so the fingerprints are a *column
reduction over the source batch* — they can be accumulated chunk by chunk as
the multi-source driver (core/multisource.py) streams converged label
matrices, without ever gathering the dense n x n pattern.

The kernel follows the same VREG-shaped blocking idiom as gsofa_relax.py:
grid ``(V/Bv, S/Bs)`` with the source axis innermost, so each (8, Bv) output
tile stays resident in VMEM while the (Bs, Bv) label tiles stream past it.
The three fingerprint lanes live in rows 0..2 of an (8, V) output (the 8-row
sublane pad is free at int32 tile granularity); row 0 accumulates with ``+``,
row 1 with wrapping ``+``, row 2 with ``^`` — all associative, so the S-axis
grid accumulation is race-free by construction.

Tiling constraints: last dim multiples of 128 (or the whole array dim, as
for the 8-lane meta block), second-to-last multiples of 8 (int32 VREG shape
8 x 128).  VMEM per step: ``Bs*Bv + 8*Bs + 8*Bv`` int32 elements; defaults
(8, 512) -> ~20 KB << 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fp_kernel(rel_ref, meta_ref, out_ref, *, block_s: int, block_v: int):
    """Grid (V/Bv, S/Bs); accumulate fingerprints over the S axis (axis 1).

    rel_ref:  (Bs, Bv) int32 — offset-free labels: maxId, or n+1 when the
              label is uninitialized/stale (precomputed by the ops.py wrapper
              so no SMEM scalar is needed in the hot loop).
    meta_ref: (Bs, 8) int32 — per-source lanes: lane 0 = source id, lane 1 =
              mix1(source), lane 2 = mix2(source), lane 3 = 1 for real rows
              (0 for batch padding); lanes 4..7 are padding.
    out_ref:  (8, Bv) int32 — row 0 count, row 1 hash-sum, row 2 hash-xor.
    """
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rel = rel_ref[...]                                   # (Bs, Bv)
    meta = meta_ref[...]                                 # (Bs, 8)
    src = meta[:, 0:1]                                   # (Bs, 1)
    m1 = meta[:, 1:2]
    m2 = meta[:, 2:3]
    valid = meta[:, 3:4]

    col = (pl.program_id(0) * block_v
           + jax.lax.broadcasted_iota(jnp.int32, rel.shape, 1))
    # Theorem-1 fill test (maxId[v] < v) restricted to the strictly-lower
    # triangle (source row below the column's diagonal).
    mask = (rel < col) & (src > col) & (valid != 0)      # (Bs, Bv)

    cnt = jnp.sum(mask.astype(jnp.int32), axis=0)        # (Bv,)
    hsum = jnp.sum(jnp.where(mask, jnp.broadcast_to(m1, rel.shape), 0), axis=0)
    xor_terms = jnp.where(mask, jnp.broadcast_to(m2, rel.shape), 0)
    # static unroll: Mosaic has no dynamic row index into a value
    hxor = xor_terms[0]
    for i in range(1, block_s):
        hxor = hxor ^ xor_terms[i]

    row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    cur = out_ref[...]
    out_ref[...] = jnp.where(
        row == 0, cur + cnt[None, :],
        jnp.where(row == 1, cur + hsum[None, :],
                  jnp.where(row == 2, cur ^ hxor[None, :], cur)))


@functools.partial(
    jax.jit, static_argnames=("block_s", "block_v", "interpret"),
)
def supernode_fp_pallas(rel: jax.Array, meta: jax.Array, *, block_s: int = 8,
                        block_v: int = 512, interpret: bool = True) -> jax.Array:
    """(8, V) fingerprint accumulator from a (S, V) relative-label chunk.

    rel:  (S, V) int32 — ``maxId`` of each (source, vertex), with
          uninitialized/stale labels clamped to n+1 (> any column id).
    meta: (S, 8) int32 — see ``_fp_kernel``.
    Shapes must be padded to block multiples by the wrapper (ops.py).
    """
    s, v = rel.shape
    assert meta.shape == (s, 8), (meta.shape, rel.shape)
    assert s % block_s == 0 and v % block_v == 0

    grid = (v // block_v, s // block_s)
    kernel = functools.partial(_fp_kernel, block_s=block_s, block_v=block_v)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_s, block_v), lambda j, i: (i, j)),
            pl.BlockSpec((block_s, 8), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((8, block_v), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((8, v), jnp.int32),
        interpret=interpret,
    )(rel, meta)
