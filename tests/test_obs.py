"""The observability layer (repro.obs, DESIGN.md §12): span tracing,
Chrome trace export, the metrics registry, and the zero-overhead disabled
mode.

The two contracts under test:

* **enabled** — spans nest correctly across threads and tracks, the Chrome
  export is schema-valid with one pid per device track, and the metrics
  the pipeline records are *identical* between a single-device analyze and
  the 8-virtual-device sharded analyze (fill nnz, supernode histogram) —
  observability must not observe different numbers on different meshes.
* **disabled** — ``span()`` is a module-bool check returning a cached
  singleton; no span object is ever constructed, no tracer exists, and
  the registry stays empty through a full analyze/factorize.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.obs import metrics as om
from repro.obs import trace as ot


@pytest.fixture(autouse=True)
def _clean_obs_state():
    # never leak an enabled tracer or registry contents across tests
    ot.disable()
    om.registry().reset()
    yield
    ot.disable()
    om.registry().reset()


# ---- span tracing --------------------------------------------------------

def test_span_nesting_and_ordering():
    with ot.tracing() as tr:
        with ot.span("outer"):
            with ot.span("inner"):
                pass
            with ot.span("inner"):
                pass
        with ot.span("sibling"):
            pass
    s = tr.summary()
    outer = s.find("outer")
    assert outer is not None and outer.count == 1
    inner = outer.find("inner")
    assert inner is not None and inner.count == 2
    # sibling is a top-level child, not swallowed by outer
    assert outer.find("sibling") is None
    assert s.find("sibling") is not None
    # children's time is contained in the parent's
    assert inner.total_s <= outer.total_s + 1e-9
    # the rendered tree carries the same data
    text = str(s)
    assert "outer" in text and "inner" in text and "x2" in text


def test_traced_decorator_records_function_span():
    @ot.traced()
    def work():
        return 7

    with ot.tracing() as tr:
        assert work() == 7
    assert tr.phase_totals()["work"]["count"] == 1
    assert work() == 7          # and still works with tracing off


def test_mark_scopes_summary_and_phase_totals():
    with ot.tracing() as tr:
        with ot.span("before"):
            pass
        mark = tr.mark()
        with ot.span("after"):
            pass
        s = tr.summary(mark)
        totals = tr.phase_totals(mark)
    assert s.find("after") is not None
    assert s.find("before") is None
    assert list(totals) == ["after"]


def test_thread_safety():
    n_threads, per_thread = 8, 50

    def work():
        for _ in range(per_thread):
            with ot.span("t_outer"):
                with ot.span("t_inner"):
                    pass

    with ot.tracing() as tr:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    totals = tr.phase_totals()
    assert totals["t_outer"]["count"] == n_threads * per_thread
    assert totals["t_inner"]["count"] == n_threads * per_thread
    # per-thread nesting stayed coherent despite the shared event list
    s = tr.summary()
    assert s.find("t_outer").find("t_inner") is not None


def test_chrome_trace_schema(tmp_path):
    path = tmp_path / "trace.json"
    with ot.tracing(str(path)) as tr:
        with ot.span("analyze"):
            pass
        for d in (0, 1):
            with ot.device_track(d):
                with ot.span("factor_segment"):
                    pass
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    for e in xs:
        assert {"name", "ts", "dur", "pid", "tid"} <= e.keys()
        assert e["ts"] >= 0 and e["dur"] >= 0
    # one named track per pid: main + both device tracks
    pid_of = {m["args"]["name"]: m["pid"] for m in ms}
    assert {"main", "device 0", "device 1"} <= pid_of.keys()
    assert len(set(pid_of.values())) == len(pid_of)
    seg_pids = {e["pid"] for e in xs if e["name"] == "factor_segment"}
    assert seg_pids == {pid_of["device 0"], pid_of["device 1"]}
    assert {e["pid"] for e in xs if e["name"] == "analyze"} == {
        pid_of["main"]}


def test_ensure_never_tears_down_outer_tracer():
    with ot.tracing() as outer:
        with ot.ensure(True) as tr:
            assert tr is outer
        assert ot.ENABLED          # outer block still owns the tracer
    assert not ot.ENABLED
    with ot.ensure(False) as tr:
        assert tr is None and not ot.ENABLED
    with ot.ensure(True) as tr:
        assert tr is not None and ot.ENABLED
    assert not ot.ENABLED          # ensure-installed tracer torn down


# ---- metrics registry ----------------------------------------------------

def test_counter_gauge_math_and_numpy_normalization():
    reg = om.MetricsRegistry()
    reg.count("c")
    reg.count("c", 2.5)
    reg.count("c", np.int64(2))
    assert reg.get("c") == 5.5
    reg.gauge("g", np.float64(3.0))
    reg.gauge("g", 4.0)            # gauges overwrite
    assert reg.get("g") == 4.0
    # the snapshot must be plain-JSON serializable (no numpy scalars)
    json.dumps(reg.snapshot())


def test_histogram_math():
    h = om.Histogram()
    for v in range(1, 11):
        h.record(v)
    assert h.count == 10
    assert h.mean == pytest.approx(5.5)
    assert (h.min, h.max) == (1.0, 10.0)
    d = h.to_dict()
    assert set(d) == {"count", "mean", "min", "max", "p50", "p90"}
    assert 4.0 <= d["p50"] <= 6.0 and d["p90"] >= 8.0
    # beyond the kept sample only the moments update
    h2 = om.Histogram(keep=4)
    for v in range(100):
        h2.record(v)
    assert h2.count == 100 and len(h2.values) == 4
    assert h2.mean == pytest.approx(49.5)


def test_fraction_of_peak_math():
    peaks = {"mem_bw_gbs": 10.0, "flops_gflops": 100.0}
    rep = om.fraction_of_peak(5e9, 1.0, peaks, flops=50e9)
    assert rep["achieved_gbs"] == pytest.approx(5.0)
    assert rep["bw_fraction"] == pytest.approx(0.5)
    assert rep["achieved_gflops"] == pytest.approx(50.0)
    assert rep["flop_fraction"] == pytest.approx(0.5)
    assert rep["intensity_flops_per_byte"] == pytest.approx(10.0)
    # no measured time -> zero rates, not a ZeroDivisionError
    assert om.fraction_of_peak(1e9, 0.0, peaks)["achieved_gbs"] == 0.0


def test_progress_meter_eta():
    calls = []
    meter = om.ProgressMeter(lambda d, t, eta: calls.append((d, t, eta)))
    meter.update(1, 4)
    meter.update(2, 4)
    assert calls[0][:2] == (1, 4) and calls[0][2] is None
    assert calls[1][:2] == (2, 4)
    assert calls[1][2] is None or calls[1][2] >= 0.0


# ---- disabled mode: zero-overhead contract -------------------------------

def test_disabled_span_is_cached_singleton(monkeypatch):
    assert not ot.ENABLED
    assert ot.span("a") is ot.span("b") is ot._NULL_SPAN
    # prove no _Span is ever constructed on the disabled path
    class Boom:
        def __init__(self, *a, **k):
            raise AssertionError("span constructed while tracing disabled")
    monkeypatch.setattr(ot, "_Span", Boom)
    with ot.span("anything"):
        pass
    with ot.device_track(3):
        pass
    assert ot.tracer() is None


def test_disabled_pipeline_records_nothing():
    from repro.api import LUOptions, analyze
    from repro.sparse import grid2d_laplacian
    from repro.sparse.numeric import generic_values

    a = grid2d_laplacian(6)
    plan = analyze(a, LUOptions(concurrency=32))
    factor = plan.factorize(generic_values(a))
    assert plan.stats is None and factor.stats is None
    assert om.registry().snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}}


def test_lu_options_trace_populates_stats():
    from repro.api import LUOptions, analyze
    from repro.sparse import grid2d_laplacian
    from repro.sparse.numeric import generic_values

    a = grid2d_laplacian(6)
    plan = analyze(a, LUOptions(concurrency=32, trace=True))
    assert not ot.ENABLED          # analyze's ensure() tore tracing down
    assert plan.stats is not None
    for phase in ("analyze", "fixpoint", "build_schedule"):
        assert plan.stats.find(phase) is not None, phase
    factor = plan.factorize(generic_values(a))
    assert factor.stats is not None
    assert factor.stats.find("factorize") is not None
    assert factor.stats.find("factor_level") is not None
    # the registry saw the traced run
    assert om.registry().get("fill.lu_nnz") > 0


# ---- the JAX profiler: program spans on the device clock -----------------

def _small_session(backend="numpy", nx=6):
    """analyze -> factorize -> solve on a small grid; returns both results."""
    from repro.api import LUOptions, analyze
    from repro.sparse import grid2d_laplacian
    from repro.sparse.numeric import generic_values

    a = grid2d_laplacian(nx)
    plan = analyze(a, LUOptions(concurrency=32, numeric_backend=backend))
    factor = plan.factorize(generic_values(a))
    factor.solve(np.ones(a.n))
    return plan, factor


def _host_events(trace_dir):
    """{span name: [stats dict, ...]} of the ``repro.`` host events."""
    import glob

    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


def test_profiler_session_records_program_spans(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        plan, factor = _small_session(backend="kernel")   # interpret mode
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    for name in ("analyze", "fixpoint", "pattern_collect", "factorize",
                 "panel_prepare", "panel_gemm", "panel_finish", "solve",
                 "residual", "fetch", "put"):
        assert "repro." + name in events, name
    fetches = events["repro.fetch"]
    assert all(st["bytes"] > 0 and st["what"] for st in fetches)
    assert {"chunk counts", "chunk mask", "panel update"} <= {
        st["what"] for st in fetches}
    assert all(st["bytes"] > 0 for st in events["repro.put"])
    # the profiler alone asked: nothing reaches a Tracer or the registry
    assert plan.stats is None and factor.stats is None
    assert om.registry().snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}}
    assert not ot.SPANS and not ot.ENABLED


class _CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts the profiler
    checks and the annotations built."""

    checks = 0
    built = []
    collecting = True

    def __init__(self, name, **args):
        type(self).built.append(name)

    @classmethod
    def is_enabled(cls):
        cls.checks += 1
        return cls.collecting

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def counting_annotation(monkeypatch):
    class Counting(_CountingAnnotation):
        checks = 0
        built = []
    monkeypatch.setattr(ot, "TraceAnnotation", Counting)
    return Counting


def test_disabled_never_builds_trace_annotation(counting_annotation):
    counting_annotation.collecting = False

    def boom(self, name, **args):
        raise AssertionError(f"annotation {name} built while disabled")
    counting_annotation.__init__ = boom
    _small_session()
    assert counting_annotation.checks == 3     # analyze, factorize, solve


def test_profiler_checked_once_per_public_call(counting_annotation):
    from repro.api import LUOptions, analyze
    from repro.sparse import grid2d_laplacian
    from repro.sparse.numeric import generic_values

    a = grid2d_laplacian(10)
    plan = analyze(a, LUOptions(concurrency=32))
    assert counting_annotation.checks == 1
    counting_annotation.checks, counting_annotation.built[:] = 0, []
    factor = plan.factorize(generic_values(a))
    assert plan.n_levels > 5
    assert counting_annotation.checks == 1
    built = counting_annotation.built
    assert built.count("repro.factor_level") == plan.n_levels
    assert len(built) > 3 * plan.n_levels
    counting_annotation.checks = 0
    factor.solve(np.ones(a.n))
    assert counting_annotation.checks == 1
    # profiler-only spans leave no Tracer behind and no stats
    assert plan.stats is None and factor.stats is None
    assert not ot.SPANS and ot.tracer() is None


def test_profiler_and_tracing_together_fill_both(counting_annotation):
    from repro.api import LUOptions, analyze
    from repro.sparse import grid2d_laplacian

    plan = analyze(grid2d_laplacian(6), LUOptions(concurrency=32,
                                                  trace=True))
    assert plan.stats is not None and plan.stats.find("fixpoint")
    assert "repro.fixpoint" in counting_annotation.built
    assert om.registry().get("transfer.bytes_to_host") > 0
    assert not ot.SPANS and not ot.ENABLED


def test_ensure_restores_the_outer_profiler_state(counting_annotation):
    with ot.ensure():
        assert ot.SPANS and not ot.ENABLED and ot.tracer() is None
        counting_annotation.collecting = False
        with ot.ensure():
            assert not ot.SPANS
        assert ot.SPANS                # the outer call still annotates
    assert not ot.SPANS
    assert counting_annotation.checks == 2


def test_fetch_and_put_count_bytes_only_under_tracing():
    import jax.numpy as jnp

    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    d = ot.put(x, "test")                        # spans off: no span
    assert np.array_equal(ot.fetch(d, "test"), x)
    a, b = ot.fetch((d, d[0]), "pair")
    assert np.array_equal(a, x) and np.array_equal(b, x[0])
    assert om.registry().snapshot()["counters"] == {}
    with ot.tracing() as tr:
        d = ot.put(x, "test")
        assert ot.put(d, "again") is d           # already on the device
        assert ot.fetch(x, "host") is x          # already on the host
        ot.fetch((d, jnp.ones(2, jnp.int32)), "pair")
    reg = om.registry()
    assert reg.get("transfer.bytes_to_device") == x.nbytes
    assert reg.get("transfer.bytes_to_host") == x.nbytes + 8
    totals = tr.phase_totals()
    assert totals["put"]["count"] == 1 and totals["fetch"]["count"] == 1


# ---- metrics parity: single device vs 8 virtual devices ------------------

_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, "src")
import json
import jax
assert len(jax.devices()) == 8, len(jax.devices())

from repro import obs
from repro.api import LUOptions, analyze
from repro.launch.mesh import make_flat_mesh
from repro.sparse import circuit_like, permute_csr, rcm_order

a = circuit_like(400, seed=11)
a = permute_csr(a, rcm_order(a))
opts = LUOptions(concurrency=64, supernode_relax=2)

def traced_metrics(mesh):
    obs.registry().reset()
    with obs.tracing():
        analyze(a, opts, mesh=mesh)
    return obs.registry().snapshot()

single = traced_metrics(None)
dist = traced_metrics(make_flat_mesh())
out = {}
for label, snap in (("single", single), ("dist", dist)):
    out[f"fill_{label}"] = snap["gauges"]["fill.lu_nnz"]
    out[f"input_{label}"] = snap["gauges"]["fill.input_nnz"]
    out[f"sn_count_{label}"] = snap["gauges"]["supernodes.count"]
    out[f"sn_hist_{label}"] = snap["histograms"]["supernodes.size"]
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def parity(tmp_path_factory):
    path = tmp_path_factory.mktemp("obs") / "parity.py"
    path.write_text(_PARITY_SCRIPT)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"      # never the chip its parent holds
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, str(path)], capture_output=True,
                       text=True, timeout=1200, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_metrics_parity_fill_nnz(parity):
    assert parity["fill_single"] == parity["fill_dist"] > 0
    assert parity["input_single"] == parity["input_dist"] > 0


def test_metrics_parity_supernode_histogram(parity):
    assert parity["sn_count_single"] == parity["sn_count_dist"] > 0
    assert parity["sn_hist_single"] == parity["sn_hist_dist"]
