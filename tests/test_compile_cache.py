"""Where the persistent compilation cache writes (runtime/compile_cache.py).

Each case runs in its own CPU-only interpreter, because the cache
directory is process-wide JAX configuration.
"""
import os
import subprocess
import sys

import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = r"""
import sys
import jax
import jax.numpy as jnp
from repro.runtime.compile_cache import enable_compile_cache
print(enable_compile_cache(sys.argv[1]))
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [False, True],
                         ids=["checkout", "env"])
def test_compile_cache_location(tmp_path, from_env):
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_SRC)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = checkout / ".jax_cache"
    if from_env:
        want = tmp_path / "outside"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    r = subprocess.run([sys.executable, "-c", _SCRIPT, str(checkout)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.splitlines()[-1] == str(want)
    assert want.is_dir() and any(want.iterdir())
    # the cache is written in one place only
    assert os.listdir(checkout) == ([] if from_env else [".jax_cache"])
