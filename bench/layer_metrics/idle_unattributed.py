"""Share of the traced window's device-idle seconds, in %, that no program
phase names: the innermost ``repro.`` span over the idle interval is none,
or one of the spans that only hold other spans
(``bench/lib/program_spans.py::CONTAINERS``).  Summed over the chips.
Serves every split of the metric (``idle_unattributed.analyze``,
``idle_unattributed.newton``)."""
from bench.lib import program_spans


def read(ctx):
    p = program_spans.of_run()
    if p is None or p.idle_s <= 0:
        return None
    return 100.0 * p.unattributed_s / p.idle_s
