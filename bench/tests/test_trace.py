"""The trace reduction on a small trace recorded on a TPU v5e chip by
``record_trace.py``: three 1024x1024 matmuls (``jit_f``) under
``bench.matmul``, then 20 ms of host sleep and one elementwise pass
(``jit_g``) under ``bench.host``, all inside ``bench.window``."""
import os

import pytest

from bench.lib.trace import reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return reduce(TRACE)


def test_one_device_and_window(red):
    assert red.n_devices == 1
    assert 0.02 < red.window_s < 1.0
    assert red.spans == {"bench.matmul": 1, "bench.host": 1}


def test_busy_inside_window(red):
    assert 0 < red.busy_s[0] < red.window_s
    assert red.busy_mean_s == red.busy_s[0]


def test_programs_by_jit_name(red):
    f, g = red.module_seconds(r"^jit_f$"), red.module_seconds(r"^jit_g$")
    assert f > 0 and g > 0
    # the matmuls outweigh the elementwise pass; both are device work
    assert f > g
    assert f + g <= red.busy_s[0] * 1.05
    assert sum(red.op_s.values()) >= red.busy_s[0] * 0.99


def test_idle_gap_under_its_span(red):
    # the 20 ms host sleep is idle device time inside bench.host
    assert red.idle_by_span["bench.host"] >= 0.019
    idle = sum(red.idle_by_span.values())
    assert idle == pytest.approx(red.window_s - red.busy_s[0], rel=1e-6)


def test_no_collectives_on_one_chip(red):
    assert red.collective_s == 0.0
