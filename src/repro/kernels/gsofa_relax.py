"""Pallas TPU kernel: blocked (min, max)-semiring relaxation for GSoFa.

One GSoFa superstep is ``cand[s, v] = min_u (adj[u, v] ? prop[s, u] : INF)`` —
a "matmul" in the bottleneck semiring between the propagation matrix (S, U)
and the adjacency (U, V).  The MXU only accumulates (+, *), so this contraction
runs on the VPU; what the kernel buys is MXU-style *blocking*: each grid step
keeps a (Bs, Bu) prop tile, a (Bu, Bv) adjacency tile and the (Bs, Bv) output
accumulator resident in VMEM, and the U-dimension is the innermost grid axis so
the output tile is revisited (accumulated) without round-tripping to HBM.

This is the TPU adaptation of the paper's warp-centric frontier expansion
(DESIGN.md §2): the thread/warp-centric choice collapses into the block-shape
choice (Bs × Bv lanes per step), and the paper's atomicMin becomes the
associative min accumulation across U tiles.

Tiling constraints: last dim multiples of 128, second-to-last multiples of 8
(int32/float32 VREG shape 8 x 128).  VMEM footprint per step:
``Bs*Bu + Bu*Bv + Bs*Bv`` elements; defaults (8, 128, 256) -> ~140 KB << 16 MB
VMEM, leaving room for double buffering of the streamed tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import _inf


def _relax_kernel(prop_ref, adj_ref, out_ref, *, block_u: int, u_chunk: int):
    """Grid (S/Bs, V/Bv, U/Bu); accumulate min over the U axis (axis 2)."""
    inf = _inf(out_ref.dtype)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, inf)

    prop = prop_ref[...]            # (Bs, Bu)
    adj = adj_ref[...]              # (Bu, Bv)

    # Process u_chunk rows of the adjacency tile at a time: the 3-D
    # broadcast (Bs, u_chunk, Bv) stays small enough for VREGs/VMEM.  The
    # loop is unrolled with static slices (Mosaic lowers no dynamic_slice).
    acc = jnp.full_like(out_ref, inf)
    for c in range(0, block_u, u_chunk):
        p = prop[:, c:c + u_chunk]
        a = adj[c:c + u_chunk, :]
        masked = jnp.where(a[None, :, :] != 0, p[:, :, None], inf)
        acc = jnp.minimum(acc, jnp.min(masked, axis=1))
    out_ref[...] = jnp.minimum(out_ref[...], acc)


@functools.partial(
    jax.jit,
    static_argnames=("block_s", "block_u", "block_v", "u_chunk", "interpret"),
)
def minmax_relax_pallas(prop: jax.Array, adj: jax.Array, *, block_s: int = 8,
                        block_u: int = 128, block_v: int = 256, u_chunk: int = 8,
                        interpret: bool = True) -> jax.Array:
    """cand[s, v] = min_u (adj[u, v] != 0 ? prop[s, u] : INF).

    prop: (S, U) int32/float32 — already clamped & source-masked (gsofa.py).
    adj:  (U, V) any integer dtype, nonzero = edge u -> v.
    Shapes must be padded to block multiples by the wrapper (ops.py).
    """
    s, u = prop.shape
    u2, v = adj.shape
    assert u == u2, (prop.shape, adj.shape)
    assert s % block_s == 0 and u % block_u == 0 and v % block_v == 0
    assert block_u % u_chunk == 0

    grid = (s // block_s, v // block_v, u // block_u)
    kernel = functools.partial(_relax_kernel, block_u=block_u, u_chunk=u_chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_s, block_u), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_u, block_v), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_s, block_v), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((s, v), prop.dtype),
        interpret=interpret,
    )(prop, adj)
