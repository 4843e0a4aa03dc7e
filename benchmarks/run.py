"""Benchmark driver: one module per paper table/figure + the roofline probe.

    PYTHONPATH=src python -m benchmarks.run             # everything
    PYTHONPATH=src python -m benchmarks.run --only speedup,space
    PYTHONPATH=src python -m benchmarks.run --check-baseline
    PYTHONPATH=src python -m benchmarks.run --trace     # + Chrome traces
    PYTHONPATH=src python -m benchmarks.run --validate-traces

Paper-figure map:
  workload     -> Fig 3   (per-source workload growth)
  balance      -> Figs 7/8/11 (combined traversal + interleaved assignment)
  concurrency  -> Fig 12  (throughput vs #C)
  speedup      -> Fig 10  (GSoFa vs sequential fill2 baseline)
  space        -> Figs 13/14/16 + Tables II/III (memory management)
  supernode    -> §"supernode detection" (streamed fingerprints vs post-pass)
  numeric      -> DESIGN.md §4 (supernodal numeric LU vs column-at-a-time)
  solve        -> DESIGN.md §9 (packed CSC-panel storage + solve/refinement)
  refactorize  -> DESIGN.md §10 (plan reuse: analyze once, refactorize many)
  distributed  -> DESIGN.md §11 (panel placement + 8-device analyze parity)
  roofline     -> DESIGN.md §12 (machine peak probe: STREAM triad + DGEMM)
  serve        -> DESIGN.md §14 (plan cache + batched factorize/solve tier)
  robust       -> DESIGN.md §15 (static pivoting + perturbation + quality)
  blocking     -> DESIGN.md §16 (irregular blocking merge + roofline autotune)

Exits nonzero if any selected suite fails, so CI smoke steps catch wiring rot.

``--check-baseline`` is the CI regression gate: fresh ``artifacts/*.json``
are compared against the committed ``baselines/*.json``.  Machine-portable
ratio metrics (speedups) are gated at ``--tolerance`` (default 25%); absolute
times participate only with ``--check-times`` (opt-in for like-for-like
hardware).  Exits nonzero on any regression.

``--trace`` (DESIGN.md §12) wraps every selected suite in
``repro.obs.tracing``, writing a Perfetto-loadable Chrome trace to
``artifacts/trace_<suite>.json`` per suite (the registry is reset per suite
so each artifact's ``metrics`` block is that suite's own), and turns on
rate-limited stderr progress/ETA lines for the long analyzes.
``--validate-traces`` is the matching CI smoke step: every expected trace
must parse as Chrome trace-event JSON and contain at least one span for
each of the suite's required phases (wiring rot in the instrumentation
fails loudly, not by silently emitting empty traces).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# span names every suite's trace must contain at least once under --trace
# (the span taxonomy is DESIGN.md §12; suites listed with an empty set are
# parse-validated only — e.g. roofline probes record no pipeline spans)
REQUIRED_PHASES = {
    "workload": ["fixpoint_chunk"],
    "balance": ["fixpoint_chunk"],
    "concurrency": ["fixpoint_chunk"],
    "speedup": ["fixpoint_chunk"],
    "space": ["fixpoint", "fixpoint_chunk"],
    "supernode": ["fingerprint_update", "supernode_detect"],
    "numeric": ["analyze", "fixpoint", "supernode_detect", "factorize",
                "factor_level", "scatter_values"],
    "solve": ["analyze", "factorize", "solve_forward", "solve_backward"],
    "refactorize": ["analyze", "factorize", "factor_level",
                    "solve_forward"],
    "distributed": ["analyze", "placement", "factorize", "factor_level",
                    "factor_segment", "solve_forward", "solve_backward",
                    "runtime", "overlap"],
    "roofline": [],
    "serve": ["serve", "factorize_batch", "solve_batch"],
    "robust": ["analyze", "robust_prepass", "factorize", "solve_forward",
               "robust_quality"],
    "blocking": ["analyze", "factorize", "replan", "blocking_merge",
                 "autotune"],
}


def check_baseline(tolerance: float, include_times: bool,
                   baseline_dir: str | None) -> None:
    from benchmarks.common import check_baselines

    violations = check_baselines(baseline_dir=baseline_dir,
                                 tolerance=tolerance,
                                 include_times=include_times)
    if not violations:
        print(f"baseline gate: OK (tolerance {tolerance:.0%}, "
              f"times {'included' if include_times else 'excluded'})")
        return
    print(f"baseline gate: {len(violations)} violation(s)")
    for v in violations:
        print(f"  [{v['kind']}] {v['path']}: {v['detail']}")
    sys.exit(1)


def validate_traces(only: set) -> None:
    from benchmarks.common import ARTIFACTS

    names = [n for n in REQUIRED_PHASES if not only or n in only]
    failures = []
    for name in names:
        path = os.path.join(ARTIFACTS, f"trace_{name}.json")
        if not os.path.exists(path):
            failures.append(f"{name}: trace file missing ({path}) — was the "
                            f"suite run with --trace?")
            continue
        try:
            with open(path) as f:
                events = json.load(f)
        except json.JSONDecodeError as e:
            failures.append(f"{name}: trace is not valid JSON ({e})")
            continue
        if isinstance(events, dict):           # JSON-object trace format
            events = events.get("traceEvents")
        if not isinstance(events, list):
            failures.append(f"{name}: Chrome trace must be a JSON array or "
                            f"an object with a 'traceEvents' array")
            continue
        spans = [e for e in events if isinstance(e, dict)
                 and e.get("ph") == "X"]
        bad = [e for e in spans
               if not {"name", "ts", "dur", "pid", "tid"} <= e.keys()]
        if bad:
            failures.append(f"{name}: {len(bad)} complete event(s) missing "
                            f"required keys (name/ts/dur/pid/tid)")
        seen = {e["name"] for e in spans if "name" in e}
        for phase in REQUIRED_PHASES[name]:
            if phase not in seen:
                failures.append(f"{name}: no '{phase}' span in trace "
                                f"(has: {sorted(seen)[:12]})")
    if failures:
        print(f"trace validation: {len(failures)} failure(s)")
        for f in failures:
            print(f"  {f}")
        sys.exit(1)
    print(f"trace validation: OK ({len(names)} trace(s), every required "
          f"phase present)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--check-baseline", action="store_true",
                    help="compare fresh artifacts against committed "
                         "baselines and exit nonzero on regression")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed relative drift for gated metrics")
    ap.add_argument("--check-times", action="store_true",
                    help="also gate absolute wall-clock metrics (only "
                         "meaningful on the hardware that recorded the "
                         "baselines)")
    ap.add_argument("--baseline-dir", default=None)
    ap.add_argument("--trace", action="store_true",
                    help="wrap each suite in repro.obs.tracing, writing "
                         "artifacts/trace_<suite>.json, and print stderr "
                         "progress for long analyzes")
    ap.add_argument("--validate-traces", action="store_true",
                    help="validate previously written traces: Chrome "
                         "trace-event JSON with >=1 span per required phase")
    args = ap.parse_args()

    only = set(filter(None, args.only.split(",")))

    if args.check_baseline:
        check_baseline(args.tolerance, args.check_times, args.baseline_dir)
        return
    if args.validate_traces:
        validate_traces(only)
        return

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    from benchmarks import (bench_balance, bench_blocking,
                            bench_concurrency, bench_distributed,
                            bench_numeric, bench_refactorize, bench_robust,
                            bench_serve, bench_solve, bench_space,
                            bench_speedup, bench_supernode, bench_workload,
                            roofline)
    suites = [
        ("workload", bench_workload.main),
        ("balance", bench_balance.main),
        ("concurrency", bench_concurrency.main),
        ("speedup", bench_speedup.main),
        ("space", bench_space.main),
        ("supernode", bench_supernode.main),
        ("numeric", bench_numeric.main),
        ("solve", bench_solve.main),
        ("refactorize", bench_refactorize.main),
        ("distributed", bench_distributed.main),
        ("roofline", roofline.main),
        ("serve", bench_serve.main),
        ("robust", bench_robust.main),
        ("blocking", bench_blocking.main),
    ]
    if args.trace:
        import benchmarks.common as common
        from repro import obs

        common.PROGRESS = True
        os.makedirs(common.ARTIFACTS, exist_ok=True)

    failures = []
    for name, fn in suites:
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"\n===== {name} =====")
        try:
            if args.trace:
                # fresh counters per suite so each artifact's metrics block
                # is self-contained; the trace writes even if the suite
                # raises (wiring rot stays diagnosable from the artifact)
                obs.registry().reset()
                trace_path = os.path.join(common.ARTIFACTS,
                                          f"trace_{name}.json")
                with obs.tracing(trace_path):
                    fn()
            else:
                fn()
        except Exception as e:  # keep the suite running; report at the end
            print(f"[{name}] FAILED: {type(e).__name__}: {e}")
            failures.append(name)
        print(f"[{name}] {time.time()-t0:.1f}s")
    if failures:
        print(f"\nFAILED suites: {', '.join(failures)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
