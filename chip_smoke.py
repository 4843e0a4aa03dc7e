"""Run analyze -> factorize -> solve once on a TPU and check every result.

    python chip_smoke.py              # one chip: the default single-device path
    python chip_smoke.py --chips 4    # only the four-chip distributed analyze

The matrix is bbd-20k, ``bordered_block_diagonal(20000, block=16,
border=64, seed=3)``: the circuit-transient structure at the largest size
the repository runs end to end.  Checks, each of which raises on failure:

* the plan's ``lu_nnz`` and supernode count match their known values;
* the Pallas fingerprint kernel matches its jnp oracle bitwise on one label
  chunk of this matrix (on a TPU the default analyze uses the kernel);
* factorize + solve reach a relative residual <= 1e-10 on the ``numpy`` and
  ``kernel`` numeric backends, and ``x`` agrees with ``scipy.sparse.linalg
  .splu`` on the same system;
* with ``--chips 4``: the analyze sharded over four chips equals a
  one-device analyze bitwise, and its plan solves to the same bound.

Without a TPU it exits non-zero and prints no result.  The phase times it
prints are smoke timings of one cold run, compilation included, not
benchmark numbers.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

BBD = dict(n=20_000, block=16, border=64, seed=3)
BBD_LU_NNZ = 218_798
BBD_SUPERNODES = 9_372
CONCURRENCY = 512
RESIDUAL_BOUND = 1e-10
SPLU_RTOL = 1e-8


def fail(msg: str) -> None:
    raise RuntimeError(msg)


class Phases:
    """Wall seconds per phase, and the programs JAX compiled during the run."""

    def __init__(self):
        import jax

        self.seconds: dict = {}
        self.programs = 0
        self.cache_hits = 0

        def on_duration(event, _secs, **_kw):
            # fires once per program, whether compiled or read from the cache
            if event == "/jax/core/compile/backend_compile_duration":
                self.programs += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0, p0 = time.perf_counter(), self.programs
        yield
        self.seconds[name] = time.perf_counter() - t0
        print(f"[smoke timing] {name}: {self.seconds[name]:.3f} s, "
              f"{self.programs - p0} programs", flush=True)

    def report(self) -> None:
        print(f"[smoke timing] phases: {json.dumps(self.seconds)}")
        print(f"[smoke timing] programs: {self.programs}, of which compiled "
              f"{self.programs - self.cache_hits} and read from the "
              f"persistent cache {self.cache_hits}; one run, not a benchmark")


def tpu_devices(chips: int) -> list:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              f"refusing to run on it", file=sys.stderr)
        sys.exit(1)
    if len(devices) < chips:
        print(f"chip_smoke: --chips {chips} but only {len(devices)} TPU "
              f"device(s) are visible", file=sys.stderr)
        sys.exit(1)
    print(f"device: {devices[0].device_kind} x {len(devices)} "
          f"({platform})", flush=True)
    return devices


def check_solve(label: str, plan, values, b, x_ref, phases) -> None:
    import numpy as np

    with phases(f"{label}.factorize"):
        factor = plan.factorize(values)
    with phases(f"{label}.solve"):
        res = factor.solve(b)
    err = float(np.linalg.norm(res.x - x_ref) / np.linalg.norm(x_ref))
    print(f"{label}: residual {res.residual:.3e} (history "
          f"{[f'{r:.2e}' for r in res.residuals]}), |x - x_splu|/|x_splu| "
          f"{err:.3e}", flush=True)
    if not res.residual <= RESIDUAL_BOUND:
        fail(f"{label}: residual {res.residual:.3e} above {RESIDUAL_BOUND}")
    if not err <= SPLU_RTOL:
        fail(f"{label}: x differs from splu by {err:.3e} (> {SPLU_RTOL})")


def splu_reference(a, values, b):
    import scipy.sparse as sp
    from scipy.sparse.linalg import splu

    mat = sp.csr_matrix((values, a.indices, a.indptr), shape=(a.n, a.n))
    return splu(mat.tocsc()).solve(b)


def check_plan_counts(label: str, plan) -> None:
    print(f"{label}: lu_nnz {plan.lu_nnz}, supernodes {plan.n_supernodes}, "
          f"devices {plan.n_devices}", flush=True)
    if plan.lu_nnz != BBD_LU_NNZ or plan.n_supernodes != BBD_SUPERNODES:
        fail(f"{label}: lu_nnz {plan.lu_nnz} / supernodes "
             f"{plan.n_supernodes}, want {BBD_LU_NNZ} / {BBD_SUPERNODES}")


def check_fingerprint_kernel(a) -> None:
    """The Pallas fingerprint kernel vs its oracle on the last source chunk
    (the border rows, where the fill is)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.gsofa import gsofa_batch, prepare_graph
    from repro.kernels import ops as kops
    from repro.supernodes.fingerprint import mix1, mix2

    n = a.n
    srcs = np.arange(n - CONCURRENCY, n, dtype=np.int32)
    labels = gsofa_batch(prepare_graph(a), srcs).labels
    rel = jnp.where(labels <= n, labels, n + 1)
    args = (rel, jnp.asarray(srcs), jnp.asarray(mix1(srcs).view(np.int32)),
            jnp.asarray(mix2(srcs).view(np.int32)),
            jnp.ones((len(srcs),), jnp.int32))
    got = np.asarray(kops.column_fingerprints(*args))
    want = np.asarray(kops.column_fingerprints_ref(*args))
    if not np.array_equal(got, want):
        bad = np.flatnonzero((got != want).any(axis=0))
        fail(f"fingerprint kernel differs from its oracle in "
             f"{len(bad)} column(s), first {bad[:8].tolist()}")
    if not got[0].sum() > 0:
        fail("fingerprint chunk has no below-diagonal fill to compare")
    print(f"fingerprint kernel == oracle on sources {n - CONCURRENCY}.."
          f"{n - 1} ({int(got[0].sum())} fill entries)", flush=True)


def one_chip(a, values, b, x_ref, phases) -> None:
    import repro

    with phases("analyze"):
        plan = repro.analyze(a, repro.LUOptions(concurrency=CONCURRENCY))
    check_plan_counts("analyze", plan)
    with phases("fingerprint_kernel_check"):
        check_fingerprint_kernel(a)
    check_solve("numpy", plan, values, b, x_ref, phases)
    with phases("replan"):
        kplan = repro.replan(plan,
                             plan.options.replace(numeric_backend="kernel"))
    check_solve("kernel", kplan, values, b, x_ref, phases)


def four_chips(a, values, b, x_ref, devices, phases) -> None:
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.core.distributed import (assign_sources,
                                        make_distributed_chunk_step)
    from repro.core.gsofa import prepare_graph
    from repro.launch.mesh import make_flat_mesh

    mesh = make_flat_mesh()
    with phases("placement_check"):
        # one sharded chunk step: each shard's labels live on its own chip
        step = make_distributed_chunk_step(mesh, a.n)
        cols = assign_sources(a.n, mesh.size)[:, :CONCURRENCY]
        labels = step(jnp.asarray(cols), prepare_graph(a))[0]
        where = sorted(s.device.id for s in labels.addressable_shards)
        if where != sorted(d.id for d in devices[:mesh.size]):
            fail(f"label shards sit on devices {where}, not one per chip")
    print(f"label shards: one (1, {CONCURRENCY}, {a.n}) block on each of "
          f"devices {where}", flush=True)

    with phases("analyze_4chip"):
        plan4 = repro.analyze(a, repro.LUOptions(concurrency=CONCURRENCY,
                                                 distribute=True))
    with phases("analyze_1device"):
        plan1 = repro.analyze(a, repro.LUOptions(concurrency=CONCURRENCY),
                              mesh=make_flat_mesh(1))
    if plan4.n_devices != mesh.size:
        fail(f"four-chip plan placed on {plan4.n_devices} device(s)")
    for name in ("l_counts", "u_counts", "supernodes"):
        if not np.array_equal(getattr(plan4.sym, name),
                              getattr(plan1.sym, name)):
            fail(f"four-chip analyze differs from one device in {name}")
    if plan4.lu_nnz != plan1.lu_nnz:
        fail(f"four-chip lu_nnz {plan4.lu_nnz} != one-device "
             f"{plan1.lu_nnz}")
    check_plan_counts("analyze_4chip", plan4)
    print("four-chip analyze == one-device analyze bitwise (l_counts, "
          "u_counts, supernodes, lu_nnz)", flush=True)
    check_solve("numpy_4chip", plan4, values, b, x_ref, phases)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip distributed analyze "
                         "and what it is compared with")
    args = ap.parse_args()

    devices = tpu_devices(args.chips)

    from repro.runtime.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache(ROOT)}", flush=True)
    phases = Phases()

    import numpy as np

    from repro.sparse.matrices import bordered_block_diagonal
    from repro.sparse.numeric import generic_values_csr

    with phases("setup"):
        a = bordered_block_diagonal(BBD["n"], block=BBD["block"],
                                    border=BBD["border"], seed=BBD["seed"])
        values = generic_values_csr(a)
        b = np.random.default_rng(42).standard_normal(a.n)
        x_ref = splu_reference(a, values, b)

    if args.chips == 4:
        four_chips(a, values, b, x_ref, devices, phases)
    else:
        one_chip(a, values, b, x_ref, phases)
    phases.report()

    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
