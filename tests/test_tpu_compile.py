"""Compile-only checks of the main-path kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles one program at its real size
for a ``v5e:2x2`` topology that is described, not attached, and asserts
that the Pallas kernel reached the TPU compiler as a ``tpu_custom_call``
(interpret mode would lower to plain HLO instead).  This catches what the
interpret-mode tests cannot: block shapes off the (8, 128) tiling and
primitives Mosaic does not lower.

The topology is described only inside the ``topo`` fixture, so importing
this module loads no TPU library; the fixture skips where the topology
cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.gsofa import SymbolicGraph, _fixpoint
from repro.kernels.gsofa_relax import minmax_relax_pallas
from repro.kernels.panel_update import (panel_update_batched_pallas,
                                        panel_update_pallas)
from repro.kernels.supernode_fp import supernode_fp_pallas

# bbd-20k: bordered_block_diagonal(20000, block=16, border=64, seed=3)
BBD_N, BBD_C, BBD_K = 20_000, 512, 12


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **kwargs) -> str:
    return jax.jit(fn, **kwargs).lower(*args).compile().as_text()


def test_supernode_fp_compiles(one_chip):
    s, v = BBD_C, 20_480
    text = _compiled_text(
        lambda rel, meta: supernode_fp_pallas(rel, meta, block_s=8,
                                              block_v=512, interpret=False),
        _sds((s, v), jnp.int32, one_chip), _sds((s, 8), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("adj_dtype", [jnp.uint8, jnp.int32],
                         ids=["uint8", "int32"])
def test_gsofa_relax_compiles(one_chip, adj_dtype):
    text = _compiled_text(
        lambda prop, adj: minmax_relax_pallas(prop, adj, interpret=False),
        _sds((64, 4096), jnp.int32, one_chip),
        _sds((4096, 4096), adj_dtype, one_chip))
    assert "tpu_custom_call" in text


def test_panel_update_compiles(one_chip):
    m = 512
    args = [_sds((m, m), jnp.float32, one_chip) for _ in range(3)]
    text = _compiled_text(
        lambda acc, l, u: panel_update_pallas(acc, l, u, interpret=False),
        *args)
    assert "tpu_custom_call" in text


def test_panel_update_batched_compiles(one_chip):
    args = [_sds((16, 256, 256), jnp.float32, one_chip) for _ in range(3)]
    text = _compiled_text(
        lambda acc, l, u: panel_update_batched_pallas(acc, l, u,
                                                      interpret=False),
        *args)
    assert "tpu_custom_call" in text


def test_ell_fixpoint_compiles_at_bbd20k(one_chip):
    """The default analyze's fixpoint (ELL gather, no kernel) at bbd-20k
    shapes: it must compile and fit one chip's 16 GB."""
    i32 = jnp.int32
    graph = SymbolicGraph(
        n=BBD_N,
        in_ell=_sds((BBD_N, BBD_K), i32, one_chip),
        out_ell=_sds((BBD_N, BBD_K), i32, one_chip),
        out_deg=_sds((BBD_N,), i32, one_chip))
    lowered = _fixpoint.lower(
        graph, _sds((BBD_C,), i32, one_chip),
        _sds((BBD_C, BBD_N), i32, one_chip), _sds((), i32, one_chip),
        backend="ell", max_iters=BBD_N + 2)
    mem = lowered.compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16e9, total
