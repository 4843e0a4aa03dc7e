"""Distributed GSoFa on 8 host devices (subprocess: device count is locked at
jax init, so multi-device tests run in their own interpreter)."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import json
import numpy as np
import jax
from repro.sparse import circuit_like
from repro.core.theory import elimination_fill
from repro.core.gsofa import prepare_graph
from repro.core.distributed import assign_sources, distributed_symbolic

a = circuit_like(160, seed=6)
e = elimination_fill(a); np.fill_diagonal(e, False)
ids = np.arange(a.n)
l_ref = (e & (ids[None, :] < ids[:, None])).sum(1)
u_ref = (e & (ids[None, :] > ids[:, None])).sum(1)
g = prepare_graph(a)
mesh = jax.make_mesh((8,), ("src",))
out = {}
for pol in ("interleave", "contiguous"):
    r = distributed_symbolic(g, mesh, policy=pol)
    out[pol] = {
        "correct": bool(np.array_equal(r["l_counts"], l_ref)
                        and np.array_equal(r["u_counts"], u_ref)),
        "balance": float(r["balance_ratio"]),
    }
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def dist_result():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"      # never the chip its parent holds
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_distributed_correct_both_policies(dist_result):
    assert dist_result["interleave"]["correct"]
    assert dist_result["contiguous"]["correct"]


def test_interleave_balances_edge_checks(dist_result):
    """Paper Fig 8: round-robin source assignment flattens the inter-device
    workload ratio (paper: 10.31 -> 1.01; threshold is generous)."""
    assert dist_result["contiguous"]["balance"] > 5.0
    assert dist_result["interleave"]["balance"] < 2.0


def test_assign_sources_shapes():
    from repro.core.distributed import assign_sources
    m = assign_sources(10, 4, policy="interleave")
    assert m.shape == (4, 3)
    assert m[1, 0] == 1 and m[1, 1] == 5  # strided
    c = assign_sources(10, 4, policy="contiguous")
    assert c[0, 0] == 0 and c[0, 2] == 2
    # padding repeats the last valid source
    assert m.max() == 9 and c.max() == 9
