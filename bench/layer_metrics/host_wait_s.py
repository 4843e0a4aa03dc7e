"""Seconds per unit the host spends in the program's ``fetch`` spans:
waiting for the device to finish what a read needs, plus the copy to the
host (``repro.obs.fetch``).  Serves every split (``host_wait_s.analyze``,
``host_wait_s.newton``)."""
from bench.lib import program_spans


def read(ctx):
    p = program_spans.of_run()
    return None if p is None else p.total("fetch") / ctx.units
