"""Multi-device behavior on 8 forced host devices (subprocess — the device
count must be set before jax initializes, so these run out-of-process).

Covers: ring all-reduce (exact + compressed), distributed GSoFa with
interleaved sources (balance + counts equality vs single-device), and a
data+tensor-parallel train step whose loss matches the 1-device run.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, "src")
import json
import jax, jax.numpy as jnp
import numpy as np
out = {}

# --- ring all-reduce ---
from repro.runtime.collectives import make_ring_allreduce
from jax.sharding import AxisType
mesh1 = jax.make_mesh((8,), ("x",), axis_types=(AxisType.Auto,))
x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 500)), jnp.float32)
want = np.asarray(x).sum(0)
got = np.asarray(make_ring_allreduce(mesh1, "x")(x))
out["ring_exact_err"] = float(np.abs(got - want[None]).max())
gotc = np.asarray(make_ring_allreduce(mesh1, "x", compress=True)(x))
out["ring_int8_rel_err"] = float(np.abs(gotc - want[None]).max()
                                 / np.abs(want).max())

# --- distributed GSoFa: interleaved sources over 8 devices ---
from repro.core.distributed import distributed_symbolic
from repro.core.gsofa import prepare_graph
from repro.core.multisource import run_multisource
from repro.sparse import paper_dataset_analogue, permute_csr, rcm_order
a = permute_csr(paper_dataset_analogue("TT"), rcm_order(paper_dataset_analogue("TT")))
graph = prepare_graph(a)
res_i = distributed_symbolic(graph, mesh1, policy="interleave")
res_c = distributed_symbolic(graph, mesh1, policy="contiguous")
single = run_multisource(graph, concurrency=64)
out["gsofa_counts_match"] = bool(
    (res_i["l_counts"] == single.l_counts).all()
    and (res_i["u_counts"] == single.u_counts).all())
out["balance_interleave"] = float(res_i["balance_ratio"])
out["balance_contiguous"] = float(res_c["balance_ratio"])

# --- DP x TP train step equals single-device ---
from repro.configs.base import ShapeConfig, get_config
from repro.data import make_batch_for
from repro.models import transformer as tf
from repro.train.optimizer import init_adamw
from repro.train.steps import make_train_step
cfg = get_config("qwen3-1.7b").reduced()
shape = ShapeConfig("s", 16, 4, "train")
batch = {k: jnp.asarray(v) for k, v in make_batch_for(cfg, shape).items()}
params = tf.init_params(jax.random.key(0), cfg, jnp.float32)
losses = {}
for name, axes in (("dp_tp", (4, 2)), ("single", (1, 1))):
    n_dev = axes[0] * axes[1]
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:n_dev]).reshape(axes), ("data", "model"))
    step = make_train_step(cfg, mesh, shape, dtype=jnp.float32, donate=False)
    p, o, m = step.fn(params, init_adamw(params), batch)
    losses[name] = float(m["loss"])
out["loss_dp_tp"] = losses["dp_tp"]
out["loss_single"] = losses["single"]
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    path = tmp_path_factory.mktemp("md") / "script.py"
    path.write_text(_SCRIPT)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"      # never the chip its parent holds
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, str(path)], capture_output=True,
                       text=True, timeout=1200, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_ring_allreduce_exact(results):
    assert results["ring_exact_err"] < 1e-4


def test_ring_allreduce_int8(results):
    assert results["ring_int8_rel_err"] < 0.05


def test_distributed_gsofa_counts_match_single_device(results):
    assert results["gsofa_counts_match"]


def test_interleave_beats_contiguous(results):
    assert results["balance_interleave"] < 2.0
    assert results["balance_contiguous"] > 3.0


def test_dp_tp_loss_matches_single_device(results):
    assert abs(results["loss_dp_tp"] - results["loss_single"]) < 1e-3
