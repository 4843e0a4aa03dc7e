"""Panel GEMM dispatches per Newton step: the count of the program's
``panel_gemm`` spans, one per kernel dispatch (pad, put, launch, fetch)."""
from bench.lib import program_spans


def read(ctx):
    p = program_spans.of_run()
    if p is None or not p.count.get("panel_gemm"):
        return None
    return p.count["panel_gemm"] / ctx.units
