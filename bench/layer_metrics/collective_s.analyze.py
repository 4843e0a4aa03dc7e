"""Device seconds per analyze in collective operations (the fingerprint
merges of ``runtime/collectives.py`` and any exchange that
``core/distributed.py``'s sharded step holds), averaged over the chips."""


def read(ctx):
    r = ctx.reduction
    if r.collective_s <= 0:
        return None
    return r.collective_s / r.n_devices / ctx.units
