"""Seconds per ``factor.solve(b)`` (``numeric/solve.py``: triangular sweeps
and refinement): the benchmark's own span, closed once the device has run
all it was given."""


def read(ctx):
    s = ctx.run.solve_s
    return sum(s) / len(s) if s else None
