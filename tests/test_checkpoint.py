"""Checkpointing: save/restore round trip, torn-write safety, retention,
async writes, elastic re-shard, end-to-end restart equivalence."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, load_checkpoint, save_checkpoint
from repro.checkpoint.io import latest_step
from repro.configs.base import ShapeConfig, get_config
from repro.data import make_batch_for
from repro.models import transformer as tf
from repro.train.optimizer import init_adamw
from repro.train.steps import make_train_step


def _tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32),
                  "d": [jnp.zeros(()), jnp.full((2,), 7.0)]}}


def test_round_trip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 5, t, extra={"note": "x"})
    loaded, step, extra = load_checkpoint(str(tmp_path), t)
    assert step == 5 and extra == {"note": "x"}
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_torn_checkpoint_ignored(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    # simulate a crash mid-write at step 2: directory without 'done'
    torn = tmp_path / "step_000000002"
    torn.mkdir()
    (torn / "manifest.json").write_text("{}")
    assert latest_step(str(tmp_path)) == 1


def test_retention_and_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    t = _tree()
    for s in (1, 2, 3, 4):
        mgr.save(s, t)
    mgr.wait()
    steps = sorted(int(n.split("_")[1]) for n in os.listdir(tmp_path)
                   if n.startswith("step_"))
    assert steps == [3, 4]


def test_elastic_reshard(tmp_path):
    """Save under one mesh, restore under another sharding (elastic)."""
    t = {"w": jnp.arange(32, dtype=jnp.float32).reshape(4, 8)}
    save_checkpoint(str(tmp_path), 1, t)
    from jax.sharding import AxisType
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = {"w": NamedSharding(mesh, P("data", None))}
    from repro.checkpoint import reshard_checkpoint
    placed, step, _ = reshard_checkpoint(str(tmp_path), t, sh)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(placed["w"]), np.asarray(t["w"]))
    assert placed["w"].sharding == sh["w"]


def test_restart_reproduces_uninterrupted_run(tmp_path):
    """Train 4 steps straight vs 2 steps -> checkpoint -> restore -> 2 steps."""
    cfg = get_config("smollm-135m").reduced()
    shape = ShapeConfig("s", 16, 2, "train")
    from jax.sharding import AxisType
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    step = make_train_step(cfg, mesh, shape, dtype=jnp.float32, donate=False)

    def batches():
        return [{k: jnp.asarray(v) for k, v in make_batch_for(cfg, shape,
                                                              step=i).items()}
                for i in range(4)]

    p = tf.init_params(jax.random.key(0), cfg, jnp.float32)
    o = init_adamw(p)
    for b in batches():
        p, o, _ = step.fn(p, o, b)
    straight = jax.tree.leaves(p)

    p2 = tf.init_params(jax.random.key(0), cfg, jnp.float32)
    o2 = init_adamw(p2)
    bs = batches()
    for b in bs[:2]:
        p2, o2, _ = step.fn(p2, o2, b)
    save_checkpoint(str(tmp_path), 2, (p2, o2))
    (p3, o3), s, _ = load_checkpoint(str(tmp_path), (p2, o2))
    assert s == 2
    p3 = jax.tree.map(jnp.asarray, p3)
    o3 = jax.tree.map(jnp.asarray, o3)
    for b in bs[2:]:
        p3, o3, _ = step.fn(p3, o3, b)
    for a, b_ in zip(straight, jax.tree.leaves(p3)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=0, atol=1e-6)
