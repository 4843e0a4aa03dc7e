"""Device seconds per analyze in the symbolic fixpoint's programs.

The fixpoint is ``core/gsofa.py::fixpoint_impl`` (jit name
``fixpoint_impl``) on one device, and the sharded chunk step of
``core/distributed.py`` (jit name ``body``) on a mesh.  Summed over the
chips, divided by the chips and by the analyses in the traced window.
"""
PROGRAMS = r"^jit_fixpoint_impl$|^jit_body$"


def read(ctx):
    r = ctx.reduction
    s = r.module_seconds(PROGRAMS)
    return s / r.n_devices / ctx.units if s > 0 else None
