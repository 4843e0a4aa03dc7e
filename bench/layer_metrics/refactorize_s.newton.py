"""Seconds per ``plan.factorize(values)`` (``numeric/supernodal.py``): the
benchmark's own span, closed once the device has run all it was given."""


def read(ctx):
    s = ctx.run.factorize_s
    return sum(s) / len(s) if s else None
