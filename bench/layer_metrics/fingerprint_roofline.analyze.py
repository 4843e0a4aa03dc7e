"""Supernode fingerprint kernel (``kernels/supernode_fp.py``): share of
the HBM roofline, in %.  Bytes it must move per analyze come from shapes
(``bench/lib/work.py::fingerprint_bytes``); its time is the device time of
the ``supernode_fp_pallas`` program over the traced window."""
import math

from bench.lib.peaks import roofline_share
from bench.lib.work import fingerprint_bytes

PROGRAMS = r"supernode_fp_pallas"


def read(ctx):
    t = ctx.reduction.module_seconds(PROGRAMS)
    if t <= 0:
        return None
    plan = ctx.run.kept[2]
    chunks = math.ceil(plan.n / plan.sym.concurrency)
    nbytes = fingerprint_bytes(plan.n, chunks) * ctx.units
    return roofline_share(0.0, nbytes, t, ctx.devices[0].device_kind)
