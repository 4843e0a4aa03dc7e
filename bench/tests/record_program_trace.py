"""Record the chip trace that ``test_program_spans.py`` reads.

    python3 bench/tests/record_program_trace.py <out.xplane.pb>

Run on a TPU.  It builds the ``bbd-20k`` configuration's pattern at
n = 1024 (seed 3), warms up one analyze and one Newton step outside the
trace, then records, inside one ``bench.window`` annotation, one
``repro.analyze`` (under ``bench.analyze``) and one Newton step:
``plan.factorize(values)`` and ``factor.solve(b)`` (under
``bench.factorize`` and ``bench.solve``).  The program's own spans reach the
trace as ``repro.*`` host events because a profiler session is collecting;
the run turns no option on.
"""
import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402

from bench.lib import patterns as P  # noqa: E402

N = 1024


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_program_trace: needs a TPU")
    import repro
    from repro.sparse.csr import CSRMatrix

    with open(os.path.join(ROOT, "bench", "configs", "bbd-20k.json")) as f:
        cfg = json.load(f)
    cfg["args"]["n"] = N
    p = P.generate(cfg, 3)
    a = CSRMatrix(p.n, p.indptr, p.indices)
    options = repro.LUOptions(**cfg["options"])
    values, b = P.base_values(p, 3), P.rhs(p.n, 3, 0)

    def analyze_and_step():
        with jax.profiler.TraceAnnotation("bench.analyze"):
            plan = repro.analyze(a, options)
        with jax.profiler.TraceAnnotation("bench.factorize"):
            factor = plan.factorize(values)
        with jax.profiler.TraceAnnotation("bench.solve"):
            factor.solve(b)

    analyze_and_step()                      # compile everything first
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0            # host annotations, no calls
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        analyze_and_step()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(path, out)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
