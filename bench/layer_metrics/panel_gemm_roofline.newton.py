"""Panel GEMM kernel (``kernels/panel_update.py``): share of its roofline,
in %.  Flops and bytes of the unpadded panel updates come from the plan's
L+U pattern and supernode partition (``bench/lib/work.py``); the time is
the device time of the ``panel_update_pallas`` and
``panel_update_batched_pallas`` programs.  The compute roof is the bf16
peak (one MXU pass at default precision, ``bench/lib/peaks.py``); the
panels are narrow, so memory bounds it."""
from bench.lib.peaks import roofline_share
from bench.lib.work import panel_gemm_work

PROGRAMS = r"panel_update(_batched)?_pallas"


def read(ctx):
    t = ctx.reduction.module_seconds(PROGRAMS)
    if t <= 0:
        return None
    plan = ctx.run.plan
    flops, nbytes = panel_gemm_work(plan.pattern.indptr, plan.pattern.rowind,
                                    plan.schedule.supernodes)
    return roofline_share(flops * ctx.units, nbytes * ctx.units, t,
                          ctx.devices[0].device_kind)
