"""Run one cell of BENCHMARK.json once and print its result.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``), whose ``kind`` selects the
module ``bench/kinds/<kind>.py``.  The run builds its inputs from the seed,
warms up every shape the window uses (set-up), calls the kind's unit of
work until ``--seconds`` have passed (the window), then checks every answer
of the window against the plain references in ``bench/lib``.

With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and the result holds
the cell's per-layer metrics, each read by ``bench/layer_metrics/<name>.py``
(or ``<base>.py``, ``<base>`` the name up to its first dot, which serves
every split of one metric).
The last line of standard output is one JSON object; the numbers the check
compared, each beside its limit, are the last lines of standard error and
the last key of that object.  Without a TPU, or with fewer chips than the
cell asks for, the run exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
# a fixed path inside the checkout
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")


class Refused(Exception):
    """The run cannot start; nothing is printed on standard output."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise Refused(f"missing {os.path.relpath(path, ROOT)}") from None


def load_module(kind: str, name: str):
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(BENCH, kind, f"{name.split('.')[0]}.py")
    if not os.path.exists(path):
        raise Refused(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = sys.modules[spec.name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its configuration, mix and metrics."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, name: str) -> "Cell":
        bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise Refused(f"no workload {name!r} in BENCHMARK.json")
        w = cells[name]
        cfg = load_json(os.path.join(BENCH, "configs", f"{w['config']}.json"))
        mix = load_json(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
        e2e = [m for m in bench["end_to_end"]
               if name in m.get("workloads", [name])]
        reported = {m["name"] for m in e2e}
        layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in
                                  reported else [])]
        return cls(name=name, chips=w["chips"], config=cfg, traffic=mix,
                   end_to_end=e2e, per_layer=layer)


def require_tpu(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips; {len(devices)} visible")
    return devices


class Programs:
    """Programs JAX compiled or read from the persistent cache."""

    def __init__(self):
        import jax

        self.total = 0
        self.cache_hits = 0

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.total += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@dataclasses.dataclass
class Context:
    """What a traffic kind's module and a per-layer reader are given."""

    cell: Cell
    seed: int
    trace: bool
    devices: list             # the chips this cell uses
    units: int = 0            # units of work finished in the window
    window_s: float = 0.0
    reduction: object = None  # bench.lib.trace.Reduction, traced runs only
    run: object = None        # the kind's Run


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: list) -> dict:
    """Set up, measure for ``seconds``, check; return the result object."""
    import jax

    from bench.lib.trace import find_xplane, reduce

    programs = Programs()
    devices = devices[:cell.chips]
    ctx = Context(cell=cell, seed=seed, trace=trace, devices=devices)
    kind = load_module("kinds", cell.traffic["kind"])
    with jax.default_device(devices[0]):
        run = ctx.run = kind.Run(ctx)
        # what set-up left behind is never scanned again by the collector,
        # so the window's collections cost the same on every run
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - T_START
        p_setup, hits_setup = programs.total, programs.cache_hits
        log(f"set-up {setup_s:.3f} s: {p_setup} programs, {hits_setup} read "
            f"from the persistent cache")
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host annotations, no calls
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while ctx.units == 0 or time.perf_counter() - t0 < seconds:
                run.unit(ctx.units)
                ctx.units += 1
            ctx.window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        gc.unfreeze()
        in_window = programs.total - p_setup
        log(f"window {ctx.window_s:.3f} s: {ctx.units} units, {in_window} "
            f"programs compiled or loaded inside the window, "
            f"{programs.cache_hits - hits_setup} of them read from the "
            f"persistent cache")
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        checks, failed = run.check()

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": None, "attempted": ctx.units, "failed": failed,
              "metrics": {}, "device": device}
    if trace:
        r = ctx.reduction = reduce(find_xplane(TRACE_DIR))
        device["busy_s"] = r.busy_mean_s
        device["window_s"] = r.window_s
        for m in cell.per_layer:
            value = load_module("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        per_chip = {k: v / r.n_devices for k, v in r.module_s.items()}
        gaps = {k: v / r.n_devices for k, v in r.idle_by_span.items()}
        result["breakdown"] = {"device_ops": r.top(per_chip),
                               "idle_gaps": r.top(gaps)}
    else:
        values = dict(run.end_to_end(ctx), setup_s=setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    result["correct"] = (failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values()))
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        cell = Cell.load(args.workload)
        devices = require_tpu(cell.chips)
    except Refused as e:
        print(f"run_cell: {e}; no result", file=sys.stderr)
        return 1
    from repro.runtime.compile_cache import enable_compile_cache

    # $JAX_COMPILATION_CACHE_DIR, or the fixed <checkout>/.jax_cache
    enable_compile_cache(ROOT)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"check correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
