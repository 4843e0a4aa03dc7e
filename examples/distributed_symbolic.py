"""Distributed + fault-tolerant GSoFa (deliverable b, example 3).

    PYTHONPATH=src python examples/distributed_symbolic.py

Runs multi-source symbolic factorization through the full production
runtime: interleaved source sharding over every available device
(shard_map), the work-stealing DynamicScheduler with a simulated straggler
and an elastic device-count change, and chunk-level checkpoint/restart
(kill the run between chunks and resume without recomputation).
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.core.distributed import distributed_symbolic
from repro.core.gsofa import prepare_graph
from repro.core.symbolic import ChunkCheckpointer, symbolic_factorize
from repro.runtime.scheduler import DynamicScheduler
from repro.sparse import economic_like, permute_csr, rcm_order


def main() -> None:
    a = economic_like(1536, seed=7)
    a = permute_csr(a, rcm_order(a))
    graph = prepare_graph(a)
    print(f"matrix: n={a.n} nnz={a.nnz}; devices: {len(jax.devices())}")

    # 1. SPMD path: interleaved sources over the device mesh
    from jax.sharding import AxisType
    mesh = jax.make_mesh((len(jax.devices()),), ("src",),
                         axis_types=(AxisType.Auto,))
    res = distributed_symbolic(graph, mesh, policy="interleave")
    print(f"distributed: balance ratio {res['balance_ratio']:.2f} "
          f"across {res['n_shards']} shard(s)")

    # 1b. the full distributed plan (DESIGN.md §11): sharded analyze ->
    # placed factorize -> placed solve, bitwise-identical to one device
    # (run under XLA_FLAGS=--xla_force_host_platform_device_count=8 to
    # see real sharding on CPU)
    import repro
    from repro.sparse.numeric import generic_values_csr

    plan = repro.analyze(a, repro.LUOptions(concurrency=256,
                                            distribute=True))
    factor = plan.factorize(generic_values_csr(a))
    b = np.random.default_rng(0).standard_normal(a.n)
    sol = factor.solve(b)
    print(f"plan: {plan.n_devices} device(s), {plan.n_supernodes} panels "
          f"in {plan.n_levels} levels, residual {sol.residual:.1e}")

    # 2. work-stealing scheduler with elastic shrink after 3 chunks
    sched = DynamicScheduler(graph, concurrency=128)
    out = sched.run(drop_devices_after=3)
    print(f"scheduler: {out['chunks']} chunks, {out['reissues']} re-issues, "
          f"elastic shrink exercised")

    # 3. checkpoint/restart: first run 'crashes' after a few chunks
    with tempfile.TemporaryDirectory() as d:
        ckpt = os.path.join(d, "chunks.jsonl")
        cp = ChunkCheckpointer(ckpt, a.n)
        full = symbolic_factorize(a, concurrency=256)
        # simulate partial progress: record only the first half of chunks
        for start in range(0, a.n // 2, 256):
            srcs = np.arange(start, min(start + 256, a.n))
            cp.record(start, srcs, full.l_counts[srcs], full.u_counts[srcs])
        resumed = symbolic_factorize(a, concurrency=256, checkpoint_path=ckpt)
        assert (resumed.l_counts == full.l_counts).all()
        assert (resumed.u_counts == full.u_counts).all()
        print("checkpoint/restart: resumed run matches uninterrupted run")

    print(f"L+U nnz = {full.lu_nnz}, fill ratio = {full.fill_ratio:.2f}")


if __name__ == "__main__":
    main()
