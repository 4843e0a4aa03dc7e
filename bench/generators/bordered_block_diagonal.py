"""Bordered block-diagonal (BBD) pattern of a partitioned circuit.

Independent ``block``-wide diagonal blocks with about three entries per row,
and ``border`` rail rows/columns at the end of the index space, each tied
symmetrically to ``couple`` random interior positions (SPICE-style BBD
order: fill stays in the blocks, the rails and the border corner).
"""
import numpy as np

from bench.lib.patterns import PATTERN, from_coo, rng


def make(*, n: int, block: int, border: int, couple: int, seed: int):
    g = rng(seed, PATTERN)
    interior = n - border
    b_rows = g.integers(0, interior, size=3 * interior)
    b_cols = np.minimum((b_rows // block) * block
                        + g.integers(0, block, size=3 * interior),
                        interior - 1)
    rails = np.repeat(np.arange(interior, n), couple)
    tied = g.integers(0, interior, size=border * couple)
    return from_coo(n, np.concatenate([b_rows, b_cols, rails, tied]),
                    np.concatenate([b_cols, b_rows, tied, rails]))
