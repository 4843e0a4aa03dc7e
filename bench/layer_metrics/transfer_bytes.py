"""Bytes per unit moved between host and device: the ``bytes`` argument
summed over the program's ``fetch`` and ``put`` spans (``repro.obs.fetch``,
``repro.obs.put``).  Serves every split (``transfer_bytes.analyze``,
``transfer_bytes.newton``)."""
from bench.lib import program_spans


def read(ctx):
    p = program_spans.of_run()
    if p is None:
        return None
    return p.arg_sum("bytes", "fetch", "put") / ctx.units
