"""Seconds per analyze in the host reduction of the fill masks
(``core/symbolic.py::PatternCollector``): self seconds of the program's
``pattern_collect`` spans, less the ``fetch`` of each mask inside them."""
from bench.lib import program_spans


def read(ctx):
    p = program_spans.of_run()
    if p is None or "pattern_collect" not in p.self_seconds:
        return None
    return p.self_seconds["pattern_collect"] / ctx.units
