"""Explicit collectives: chunked ring all-reduce with optional int8
compression — the distributed-optimization layer for slow (cross-pod) links.

GSPMD's automatic all-reduce is optimal on fast ICI; across pods the links
are the bottleneck and two classic tricks apply:

* **chunked ring** (``ppermute``): the reduce-scatter/all-gather ring is
  expressed explicitly so each chunk's transfer overlaps the reduction of
  the previous chunk (XLA pipelines successive ppermutes), and so we can
  transform the payload per hop;
* **int8 payload** with per-chunk scales: 4x fewer bytes over the link at
  the cost of quantization error on partial sums — pair with error feedback
  (train/compress.py) at the caller.

``ring_allreduce`` runs inside ``shard_map`` over one mesh axis.  With
``compress=True`` the wire format of every hop is (int8 payload, f32
scale); accumulation happens in f32 after dequantize, so error does not
compound multiplicatively with ring length.

The reduction ``op`` generalizes beyond ``add``: supernode fingerprint
shards (supernodes/fingerprint.py) merge with *mixed* reductions — counts
and hash-sums by wrapping integer addition, the xor hash by ``xor``, and
the subdiagonal/seen flags by ``max`` (boolean or).  All three are
associative and commutative, so the same reduce-scatter/all-gather ring
applies unchanged; ``merge_fingerprint_shards`` stacks the per-shard
accumulator arrays and runs one ring per accumulator — this is the
device-side merge path of distributed supernode detection
(core/distributed.py), with ``ColumnFingerprints.merge`` as its host
oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.obs import trace as _ot


_RING_OPS = ("add", "xor", "max")


def quantize(g: jax.Array) -> "tuple[jax.Array, jax.Array]":
    """Int8 wire format of one ring hop: max-abs/127 scale, symmetric
    rounding.  The live sparse runtime owns its wire codec (the train tree
    keeps an identical pair for its optimizer-boundary demo — the runtime
    must not depend on that substrate)."""
    g32 = g.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(g32)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def _combine(op: str, a: jax.Array, b: jax.Array) -> jax.Array:
    if op == "add":
        return a + b
    if op == "xor":
        return jnp.bitwise_xor(a, b)
    return jnp.maximum(a, b)


def _ring_allreduce_local(x: jax.Array, axis_name: str, *,
                          compress: bool = False,
                          op: str = "add") -> jax.Array:
    """Reduce-scatter + all-gather ring over ``axis_name`` (inside shard_map).

    x: (n*chunk,) flat per-device values (same logical tensor everywhere);
    returns the all-reduced tensor.  ``op`` picks the (associative,
    commutative) combine; int8 compression only composes with ``add``
    (quantizing xor/max payloads would corrupt exact bit reductions).
    """
    if op not in _RING_OPS:
        raise ValueError(f"unknown ring op {op!r}; pick from {_RING_OPS}")
    if compress and op != "add":
        raise ValueError(f"int8 compression only supports op='add', "
                         f"got {op!r}")
    n = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    if n == 1:
        return x
    chunks = x.reshape(n, -1)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def wire(v):
        if not compress:
            return v, jnp.float32(0)
        q, s = quantize(v)
        return q, s

    def unwire(q, s):
        return dequantize(q, s) if compress else q

    # --- reduce-scatter: after n-1 hops, device d owns the full reduction
    # of chunk (d+1) % n ---
    def rs_body(i, acc):
        # send the partial reduction of chunk (me - i), receive (me - i - 1)
        idx = (me - i) % n
        send = acc[idx]
        q, s = wire(send)
        q_r = jax.lax.ppermute(q, axis_name, perm)
        s_r = jax.lax.ppermute(s, axis_name, perm)
        recv = unwire(q_r, s_r).astype(acc.dtype)
        tgt = (me - i - 1) % n
        return acc.at[tgt].set(_combine(op, acc[tgt], recv))

    # compressed rings accumulate in f32 after dequantize; exact rings
    # (incl. the integer fingerprint merges) stay in the payload dtype
    acc0 = chunks.astype(jnp.float32) if compress else chunks
    acc = jax.lax.fori_loop(0, n - 1, rs_body, acc0)

    # --- all-gather: circulate the owned (fully reduced) chunks ---
    def ag_body(i, acc):
        idx = (me + 1 - i) % n
        send = acc[idx]
        q, s = wire(send)
        q_r = jax.lax.ppermute(q, axis_name, perm)
        s_r = jax.lax.ppermute(s, axis_name, perm)
        recv = unwire(q_r, s_r).astype(acc.dtype)
        tgt = (me - i) % n
        return acc.at[tgt].set(recv)

    acc = jax.lax.fori_loop(0, n - 1, ag_body, acc)
    return acc.reshape(x.shape).astype(x.dtype)


def make_ring_allreduce(mesh: Mesh, axis: str, *, compress: bool = False,
                        op: str = "add"):
    """Jitted ring all-reduce.

    Input: (n, k) sharded on dim 0 over ``axis`` — one summand per device.
    Output: (n, k) sharded the same way, every row holding the full
    reduction (i.e. each device's local copy of the all-reduced tensor).
    ``op``: "add" (default), "xor", or "max" — the ring pads with 0, the
    identity of all three on the non-negative payloads used here.
    """
    n = mesh.shape[axis]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=P(axis, None), out_specs=P(axis, None))
    def body(x_local):                       # (1, k) on each device
        flat = x_local.reshape(-1)
        pad = (-flat.shape[0]) % n
        if pad:
            flat = jnp.pad(flat, (0, pad))
        out = _ring_allreduce_local(flat, axis, compress=compress, op=op)
        return out[: x_local.size].reshape(x_local.shape)

    return jax.jit(body)


# ---------------------------------------------------------------------------
# distributed supernode-fingerprint merge (core/distributed.py analyze path)
# ---------------------------------------------------------------------------

def merge_fingerprint_shards(mesh: Mesh, axis: str, shards):
    """Merge per-shard ``ColumnFingerprints`` through device-side ring
    collectives: counts/hsum by wrapping integer ``add``, hxor by ``xor``,
    subdiag/seen by ``max`` (boolean or).

    ``shards`` is one ``ColumnFingerprints`` per device on the ``axis``
    (disjoint sources by construction — the distributed driver masks shard
    ownership before accumulating).  Returns a merged ``ColumnFingerprints``
    bitwise-equal to folding the shards on the host with
    ``ColumnFingerprints.merge`` (the property-tested oracle).  On a
    1-device mesh the rings are identity, so the single-device and
    multi-device analyze paths are literally the same code.
    """
    from repro.supernodes.fingerprint import ColumnFingerprints

    d = mesh.shape[axis]
    if len(shards) != d:
        raise ValueError(f"got {len(shards)} fingerprint shards for a "
                         f"{d}-device '{axis}' axis")
    n = shards[0].n
    # jax without x64 carries 32-bit integers: counts fit (<= n), and the
    # uint32 hashes wrap identically in int32 two's complement
    stack = {
        "counts": np.stack([s.counts for s in shards]).astype(np.int32),
        "hsum": np.stack([s.hsum.view(np.int32) for s in shards]),
        "hxor": np.stack([s.hxor.view(np.int32) for s in shards]),
        "subdiag": np.stack([s.subdiag for s in shards]).astype(np.int32),
        "seen": np.stack([s.seen for s in shards]).astype(np.int32),
    }
    ops = {"counts": "add", "hsum": "add", "hxor": "xor",
           "subdiag": "max", "seen": "max"}
    merged = ColumnFingerprints(n=n)
    rings = {}
    for op in set(ops.values()):
        with _ot.span("ring_build"):
            rings[op] = make_ring_allreduce(mesh, axis, op=op)
    for name, arr in stack.items():
        out = _ot.fetch(rings[ops[name]](_ot.put(arr, "fingerprint shards")),
                        "merged fingerprints")[0]
        if name == "counts":
            merged.counts = out.astype(np.int64)
        elif name == "hsum":
            merged.hsum = out.view(np.uint32).copy()
        elif name == "hxor":
            merged.hxor = out.view(np.uint32).copy()
        elif name == "subdiag":
            merged.subdiag = out.astype(bool)
        else:
            merged.seen = out.astype(bool)
    return merged
