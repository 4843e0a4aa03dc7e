"""Seconds per Newton step of host work in the numeric sweep
(``numeric/supernodal.py``): the program's ``panel_prepare`` spans (gathers
and ancestor solves) and ``panel_finish`` spans (diagonal LU and the solve
below it)."""
from bench.lib import program_spans

SPANS = ("panel_prepare", "panel_finish")


def read(ctx):
    p = program_spans.of_run()
    if p is None or not any(n in p.seconds for n in SPANS):
        return None
    return p.total(*SPANS) / ctx.units
