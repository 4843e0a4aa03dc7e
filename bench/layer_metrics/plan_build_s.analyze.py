"""Seconds per analyze in plan building (``numeric/schedule.py``,
``numeric/storage.py``): the program's ``build_schedule``, ``gather_maps``
and ``solve_schedule`` spans, which run on the host after the fixpoint's
results are on the host (``LUOptions(trace=True)`` in the traced run)."""


def read(ctx):
    s = [o.plan_build_s for o in ctx.run.outputs
         if o.plan_build_s is not None]
    return sum(s) / len(s) if s else None
