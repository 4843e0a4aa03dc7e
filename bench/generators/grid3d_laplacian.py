"""7-point stencil on an ``nx`` x ``nx`` x ``nx`` grid (the 3D Poisson model
problem); the pattern does not depend on the seed."""
import numpy as np

from bench.lib.patterns import from_coo


def make(*, nx: int, seed: int):
    del seed
    idx = np.arange(nx ** 3).reshape(nx, nx, nx)
    rows, cols = [], []
    for d in ((0, 0, 1), (0, 1, 0), (1, 0, 0)):
        a = idx[:nx - d[0], :nx - d[1], :nx - d[2]].ravel()
        b = idx[d[0]:, d[1]:, d[2]:].ravel()
        rows += [a, b]
        cols += [b, a]
    return from_coo(nx ** 3, np.concatenate(rows), np.concatenate(cols))
