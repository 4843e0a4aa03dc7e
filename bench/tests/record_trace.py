"""Record the small chip trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Run on a TPU.  Inside a ``bench.window`` annotation it runs two jitted
programs, ``f`` (a 1024 x 1024 float32 matmul, three times, under
``bench.matmul``) and ``g`` (an elementwise pass, under ``bench.host``
after 20 ms of host sleep), so the reduced trace has two programs, device
busy time, and an idle gap of at least 20 ms under ``bench.host``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def f(x):
    return x @ x


def g(x):
    return jnp.tanh(x) * 2.0


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    jf, jg = jax.jit(f), jax.jit(g)
    x = jnp.ones((1024, 1024), jnp.float32)
    jf(x).block_until_ready()
    jg(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.matmul"):
            for _ in range(3):
                jf(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host"):
            time.sleep(0.02)
            jg(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, out)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
