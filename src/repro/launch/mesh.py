"""Production meshes.

Functions, not module-level constants — importing this module never touches
jax device state; the dry-run sets the 512-placeholder-device XLA flag
before its first jax import, everything else sees the real devices.

Mesh shapes (TPU v5e target):
  * single-pod: (data=16, model=16)           — 256 chips
  * multi-pod:  (pod=2, data=16, model=16)    — 512 chips

Axis semantics across the framework:
  * ``pod``   — slow inter-pod links; batch (and FSDP for the 398B/671B
                archs) shard here; gradient compression targets this axis.
  * ``data``  — batch / ZeRO-1 optimizer sharding / sequence-sharded caches.
  * ``model`` — tensor parallelism + expert parallelism.
GSoFa shards *sources* over every axis flattened (paper's interleave, §V).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1) -> Mesh:
    """Mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


FLAT_AXIS = "shards"


def visible_device_count() -> int:
    """Number of devices jax sees right now — what ``LUPlan.place()`` and
    the dynamic runtime default to.  A function, not a constant: forced
    host-device flags and real accelerator counts are both decided at jax
    init, per process."""
    return len(jax.devices())


def make_flat_mesh(n_devices: int | None = None) -> Mesh:
    """One-axis ``(shards,)`` mesh — the distributed analyze/factorize
    substrate (DESIGN.md §11): GSoFa shards *sources* (and the plan shards
    *panels*) over the flattened device space, so a single axis is the
    whole story at any scale.

    ``n_devices=None`` takes every visible device — the same call yields a
    1-device mesh on a laptop and an 8-device mesh under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, which is
    exactly how the conformance tier runs one code path at every device
    count.  An explicit ``n_devices`` takes a prefix of
    ``jax.devices()`` (must not exceed what exists).
    """
    avail = jax.devices()
    if n_devices is None:
        n_devices = len(avail)
    if not 1 <= n_devices <= len(avail):
        raise ValueError(f"n_devices={n_devices} out of range for "
                         f"{len(avail)} visible device(s)")
    if n_devices == len(avail):
        return jax.make_mesh((n_devices,), (FLAT_AXIS,),
                             axis_types=(AxisType.Auto,))
    import numpy as np

    return Mesh(np.asarray(avail[:n_devices]), (FLAT_AXIS,))
