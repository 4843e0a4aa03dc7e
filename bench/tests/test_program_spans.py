"""The program-span reader on a trace recorded on a TPU v5e chip by
``record_program_trace.py``: one ``repro.analyze`` and one Newton step
(``factorize``, ``solve``) of the bbd configuration at n = 1024 inside
``bench.window``, with the program's spans reaching the trace because a
profiler session was collecting.

    python -m pytest bench/tests
"""
import os
import types

import pytest

from bench import run_cell as rc
from bench.lib import program_spans as S
from bench.lib.trace import reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACE = os.path.join(DATA, "program.xplane.pb")
NEW_METRICS = ("idle_unattributed.analyze", "idle_unattributed.newton",
               "host_wait_s.analyze", "host_wait_s.newton",
               "transfer_bytes.analyze", "transfer_bytes.newton",
               "pattern_collect_s.analyze", "panel_host_s.newton",
               "kernel_calls.newton")


@pytest.fixture(scope="module")
def spans():
    return S.load(TRACE)


@pytest.fixture(scope="module")
def red():
    return reduce(TRACE)


def test_one_analyze_and_one_newton_step(spans):
    for name in ("analyze", "fixpoint", "factorize", "solve"):
        assert spans.count[name] == 1, name
    assert spans.count["panel_gemm"] > 0
    assert spans.count["fixpoint_chunk"] == 2          # 1024 / 512 sources


def test_idle_attributed_and_not_add_up_to_window_minus_busy(spans, red):
    idle = red.window_s * red.n_devices - sum(red.busy_s)
    assert spans.idle_s == pytest.approx(idle, rel=1e-6)
    assert 0 <= spans.unattributed_s <= spans.idle_s


def test_busy_union_agrees_with_the_trace_reduction(spans, red):
    assert spans.n_devices == red.n_devices == 1
    assert spans.window_s == pytest.approx(red.window_s, rel=1e-9)
    assert spans.busy_s == pytest.approx(red.busy_s, rel=1e-9)
    assert sum(red.idle_by_span.values()) == pytest.approx(spans.idle_s,
                                                           rel=1e-6)


def test_transfers_carry_their_bytes(spans):
    assert spans.arg_sum("bytes", "fetch") > 0
    assert spans.arg_sum("bytes", "put") > 0
    whats = {what for name, what in spans.what_seconds if name == "fetch"}
    assert {"chunk counts", "chunk mask", "panel update"} <= whats


def test_self_seconds_lie_within_seconds(spans):
    for name, s in spans.seconds.items():
        assert -1e-9 <= spans.self_seconds[name] <= s + 1e-9, name
    # a leaf has no children
    assert spans.self_seconds["fetch"] == pytest.approx(spans.seconds["fetch"])


def test_leaf_spans_fit_inside_their_call(spans):
    sweep = spans.total("panel_prepare", "panel_gemm", "panel_finish")
    assert 0 < sweep <= spans.seconds["factorize"]
    host = (spans.self_seconds["pattern_collect"]
            + spans.total("build_schedule", "gather_maps", "solve_schedule"))
    assert 0 < host <= spans.seconds["analyze"]


def test_an_idle_interval_splits_where_the_innermost_span_changes():
    inner = S._Innermost([(0, 10, "a"), (2, 5, "b"), (3, 4, "c"),
                          (8, 12, "d")])
    assert inner.split(-2, 14) == [
        (S.NONE, 2), ("a", 2), ("b", 1), ("c", 1), ("b", 1), ("a", 3),
        ("d", 4), (S.NONE, 2)]
    assert inner.split(3.25, 3.75) == [("c", 0.5)]
    assert inner.split(20, 21) == [(S.NONE, 1)]


def test_no_phase_is_idle_longer_than_it_lasts(spans):
    for name, s in spans.idle_by_span.items():
        if name != S.NONE:
            assert s <= spans.seconds[name] * spans.n_devices + 1e-9, name


def test_a_trace_without_program_spans_reads_none():
    # the benchmark's own small trace holds bench spans only, as a
    # program without repro spans leaves it
    assert S.read(os.path.join(DATA, "small.xplane.pb")) is None


def test_every_new_metric_reads_the_recorded_trace(monkeypatch):
    monkeypatch.setattr(S, "of_run", lambda: S.load(TRACE))
    ctx = types.SimpleNamespace(units=1)
    values = {m: rc.load_module("layer_metrics", m).read(ctx)
              for m in NEW_METRICS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values["idle_unattributed.analyze"] <= 100
    assert values["kernel_calls.newton"] == float(int(
        values["kernel_calls.newton"]))


def test_every_new_metric_is_silent_without_program_spans(monkeypatch):
    monkeypatch.setattr(S, "of_run", lambda: S.load(
        os.path.join(DATA, "small.xplane.pb")))
    ctx = types.SimpleNamespace(units=1)
    for m in NEW_METRICS:
        assert rc.load_module("layer_metrics", m).read(ctx) is None, m
