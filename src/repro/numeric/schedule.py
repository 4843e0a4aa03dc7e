"""Panel-level scheduling for supernodal numeric LU (DESIGN.md §4).

The symbolic step hands over a supernode partition — contiguous ``[start,
end)`` column ranges with identical below-diagonal structure — and the
numeric step must factor those panels in an order that respects column
dependencies.  Panel J depends on panel K < J iff the filled pattern has a
structural nonzero in the U block ``U(K, J)`` (rows of K, columns of J):
exactly then does K's L panel update J.  That is the supernodal elimination
DAG (the condensation of the column etree onto supernodes).

``build_schedule`` derives, from the predicted pattern (dense bool (n, n)
or the sparse ``storage.CSCPattern`` — the sparse form is what the
O(nnz(L+U)) packed path feeds it, nothing here materializes (n, n)):

* ``ancestors[j]`` — the update list of panel j (ascending supernode ids);
  left-looking consumes it in order: solve ``U(K, J)`` against L(K, K),
  scatter into the rows of *later* ancestors, and defer the trailing rows to
  one accumulated GEMM (supernodal.py);
* ``level``/``levels`` — longest-path dependency levels: panels within a
  level share no ancestor relation and can be factored independently (batch
  unit for MXU dispatch / device assignment);
* ``partition`` — the ``pack_panels`` bin assignment (LPT or contiguous) the
  scheduler uses to group independent panels within a level; the numeric
  result is invariant to the packing policy (tests assert bitwise equality),
  only the batching/placement changes.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from repro.numeric.storage import CSCPattern
from repro.obs import metrics as _om
from repro.obs import trace as _ot
from repro.supernodes.balance import PanelPartition, pack_panels


@dataclasses.dataclass
class PanelSchedule:
    """Dependency-levelled execution plan over the supernode partition."""

    supernodes: np.ndarray        # (k, 2) [start, end) column ranges
    ancestors: List[np.ndarray]   # per panel: ascending ids of update panels
    level: np.ndarray             # (k,) dependency level of each panel
    levels: List[np.ndarray]      # panel ids per level, in execution order
    partition: PanelPartition     # pack_panels bins (batching/placement)
    col_counts: np.ndarray        # (n,) below-diagonal column counts of L

    @property
    def n_panels(self) -> int:
        return len(self.supernodes)

    @property
    def n_levels(self) -> int:
        return len(self.levels)

    def stats(self) -> dict:
        widths = self.supernodes[:, 1] - self.supernodes[:, 0]
        n_updates = sum(len(a) for a in self.ancestors)
        return {
            "n_panels": self.n_panels,
            "n_levels": self.n_levels,
            "mean_level_width": (self.n_panels / max(1, self.n_levels)),
            "max_panel_cols": int(widths.max()) if len(widths) else 0,
            "n_updates": n_updates,
            "balance_ratio": self.partition.balance_ratio,
        }


@dataclasses.dataclass(frozen=True)
class PanelPlacement:
    """Device assignment of panels for multi-device factorize/solve
    (DESIGN.md §11).

    Derived from ``pack_panels`` bins computed *per dependency level*:
    each level's panels — which are exactly the independent work of one
    sweep step — are LPT-packed by predicted L-panel nnz into
    ``n_devices`` bins, so every level's critical path is within one
    panel weight of optimal.  Within a level panels are independent
    (left-looking panels only read strictly-earlier levels), so *any*
    segment execution order yields bitwise-identical factors — placement
    changes scheduling/dispatch, never math; that is what makes factors
    invariant to the device count (the conformance-tier contract).

    Plain numpy only — plans stay picklable; the mesh itself is never
    stored (rebuild one with ``launch.mesh.make_flat_mesh`` where needed).
    """

    n_devices: int
    axis: str                      # mesh axis name (launch.mesh.FLAT_AXIS)
    device_of_panel: np.ndarray    # (k,) int64 device id per panel

    def segments(self, members: np.ndarray) -> List[np.ndarray]:
        """Per-device panel lists of one level (ascending ids within each
        segment; devices without work get empty segments)."""
        members = np.asarray(members, dtype=np.int64)
        dev = self.device_of_panel[members]
        return [np.sort(members[dev == d]) for d in range(self.n_devices)]

    def level_loads(self, schedule: "PanelSchedule") -> np.ndarray:
        """(n_levels, n_devices) packed panel weight per device per level —
        the placement-quality surface bench_distributed reports."""
        from repro.supernodes.balance import supernode_weights

        weights = supernode_weights(schedule.supernodes, schedule.col_counts)
        out = np.zeros((schedule.n_levels, self.n_devices), dtype=np.int64)
        for lv, members in enumerate(schedule.levels):
            np.add.at(out[lv], self.device_of_panel[members],
                      weights[members])
        return out


def build_placement(schedule: PanelSchedule, n_devices: int, *,
                    axis: str = "shards",
                    policy: str = "lpt") -> PanelPlacement:
    """Panel -> device assignment from per-level ``pack_panels`` bins (see
    ``PanelPlacement``).  ``n_devices=1`` degenerates to everything on
    device 0 — the same code path the conformance tier runs at every
    count."""
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    with _ot.span("placement"):
        device_of_panel = np.zeros(schedule.n_panels, dtype=np.int64)
        for members in schedule.levels:
            if not len(members):
                continue
            part = pack_panels(schedule.supernodes[members],
                               schedule.col_counts,
                               min(n_devices, len(members)), policy=policy)
            device_of_panel[members] = part.assignment
        return PanelPlacement(n_devices=n_devices, axis=axis,
                              device_of_panel=device_of_panel)


@dataclasses.dataclass
class PanelMaps:
    """Value-independent row-index maps of one panel's ancestor updates.

    Everything ``supernodal._factor_panel`` would otherwise re-derive with
    ``searchsorted`` on every factorization: the concatenated ancestor
    diagonal rows, each ancestor's (idx, hit) gather map for its L strip at
    those rows and at the panel's >= s rows, and the scatter map of the
    solved U rows back into the panel block.  Built once per analysis
    (``build_gather_maps``), replayed on every ``LUPlan.factorize`` —
    bitwise-identical math, none of the map reconstruction.
    """

    anc_rows: np.ndarray                 # concatenated ancestor diag rows
    offs: np.ndarray                     # (len(anc)+1,) strip offsets
    strip_maps: List[tuple]              # per ancestor: (idx, hit) at anc_rows[r0:]
    below_maps: List[tuple]              # per ancestor: (idx, hit) at rows >= s
    idx_j: np.ndarray                    # scatter of solved U(anc, J) into block j
    hit_j: np.ndarray


def build_panel_maps(store, schedule: PanelSchedule,
                     j: int) -> Optional[PanelMaps]:
    """Maps for one panel (``None`` when it has no ancestors)."""
    anc = schedule.ancestors[j]
    if not len(anc):
        return None
    widths = schedule.supernodes[anc, 1] - schedule.supernodes[anc, 0]
    offs = np.concatenate([[0], np.cumsum(widths)])
    anc_rows = np.concatenate([np.arange(ks, ke)
                               for ks, ke in schedule.supernodes[anc]])
    below = store.rows[j][int(store.diag[j]):]
    strip_maps = [store.local_rows(int(k), anc_rows[offs[idx]:])
                  for idx, k in enumerate(anc)]
    below_maps = [store.local_rows(int(k), below) for k in anc]
    idx_j, hit_j = store.local_rows(j, anc_rows)
    return PanelMaps(anc_rows=anc_rows, offs=offs, strip_maps=strip_maps,
                     below_maps=below_maps, idx_j=idx_j, hit_j=hit_j)


def gather_map_entries(store, schedule: PanelSchedule) -> int:
    """Entries the strip and below maps of every panel hold together.

    Ancestor ``i`` of panel j maps ``anc_rows[offs[i]:]`` and the panel's
    rows from its diagonal on, so a panel's strips sum to ``Σ_i w_i (i+1)``
    (ancestor widths ``w``) and its below maps to ``len(anc) * B_j``."""
    counts = np.fromiter(map(len, schedule.ancestors), dtype=np.int64,
                         count=schedule.n_panels)
    if not counts.sum():
        return 0
    anc = np.concatenate(schedule.ancestors)
    widths = schedule.supernodes[anc, 1] - schedule.supernodes[anc, 0]
    rank = np.arange(len(anc)) - np.repeat(np.cumsum(counts) - counts,
                                           counts)
    below = np.fromiter(map(len, store.rows), dtype=np.int64,
                        count=store.n_panels) - store.diag
    return int(widths @ (rank + 1) + counts @ below)


def _gather_maps_by_search(store, schedule: PanelSchedule
                           ) -> List[Optional[PanelMaps]]:
    """One ``local_rows`` search per (panel, ancestor) map."""
    return [build_panel_maps(store, schedule, j)
            for j in range(schedule.n_panels)]


def _gather_maps_by_table(store, schedule: PanelSchedule
                          ) -> List[Optional[PanelMaps]]:
    """The same maps read from one dense (panel, global row) table of what
    ``local_rows`` returns, built with one search per panel.

    Per panel j, ancestor ``i`` reads the table at ``q[offs[i]:]``, where
    ``q = concat(anc_rows, below)`` (strictly increasing: ancestor diagonal
    rows lie under s, the below rows from s on).  All ancestors are read in
    one gather into a flat array; each strip and below map is a view of its
    own slice of it, so the plan holds exactly the entries the searches
    would build."""
    n, sn = store.n, schedule.supernodes
    grid = np.arange(n, dtype=np.int64)
    rank = np.empty((store.n_panels, n), dtype=np.int64)
    member = np.zeros((store.n_panels, n), dtype=bool)
    for k, rows in enumerate(store.rows):
        np.minimum(np.searchsorted(rows, grid), len(rows) - 1, out=rank[k])
        member[k, rows] = True
    rank, member = rank.ravel(), member.ravel()
    maps: List[Optional[PanelMaps]] = []
    for j, anc in enumerate(schedule.ancestors):
        if not len(anc):
            maps.append(None)
            continue
        widths = sn[anc, 1] - sn[anc, 0]
        offs = np.concatenate([[0], np.cumsum(widths)])
        n_anc_rows = int(offs[-1])
        anc_rows = (np.repeat(sn[anc, 0] - offs[:-1], widths)
                    + grid[:n_anc_rows])
        q = np.concatenate([anc_rows, store.rows[j][int(store.diag[j]):]])
        lens = len(q) - offs[:-1]
        flat = np.concatenate([q[o:] for o in offs[:-1].tolist()])
        flat += np.repeat(anc * n, lens)
        idx, hit = rank[flat], member[flat]
        ends = np.cumsum(lens)
        starts = ends - lens
        mids = starts + n_anc_rows - offs[:-1]
        cuts = zip(starts.tolist(), mids.tolist(), ends.tolist())
        strip_maps, below_maps = [], []
        for a, b, c in cuts:
            strip_maps.append((idx[a:b], hit[a:b]))
            below_maps.append((idx[b:c], hit[b:c]))
        idx_j, hit_j = store.local_rows(j, anc_rows)
        maps.append(PanelMaps(anc_rows=anc_rows, offs=offs,
                              strip_maps=strip_maps, below_maps=below_maps,
                              idx_j=idx_j, hit_j=hit_j))
    return maps


def build_gather_maps(store, schedule: PanelSchedule) -> List[Optional[PanelMaps]]:
    """Precompute every panel's ancestor gather/scatter maps from the packed
    row structure — the value-independent half of ``supernodal
    ._factor_panel``, built once per analysis and replayed per factorize.

    Both builders give identical maps; the table costs ``n_panels * n``
    entries, so it is built only when the maps hold at least as many."""
    entries = gather_map_entries(store, schedule)
    table = store.n_panels * store.n <= entries
    build = _gather_maps_by_table if table else _gather_maps_by_search
    maps = build(store, schedule)
    if _ot.ENABLED:
        reg = _om.registry()
        reg.count("plan.gather_map_entries", entries)
        reg.count("plan.gather_map_table", int(table))
    return maps


def _validate_supernodes(supernodes: np.ndarray, n: int) -> np.ndarray:
    supernodes = np.asarray(supernodes, dtype=np.int64)
    if supernodes.ndim != 2 or supernodes.shape[1] != 2:
        raise ValueError(f"supernodes must be (k, 2) ranges, got "
                         f"{supernodes.shape}")
    if len(supernodes):
        if supernodes[0, 0] != 0 or supernodes[-1, 1] != n:
            raise ValueError("supernode ranges must cover [0, n)")
        if not (supernodes[1:, 0] == supernodes[:-1, 1]).all():
            raise ValueError("supernode ranges must be contiguous")
        if not (supernodes[:, 1] > supernodes[:, 0]).all():
            raise ValueError("supernode ranges must be non-empty")
    elif n:
        raise ValueError(f"no supernodes for an order-{n} matrix")
    return supernodes


def build_schedule(pattern, supernodes: np.ndarray, *,
                   n_bins: int = 8, policy: str = "lpt") -> PanelSchedule:
    """Schedule from the predicted L+U pattern and supernode ranges.

    ``pattern``: dense (n, n) bool (diagonal included — what
    ``core.gsofa.dense_pattern`` returns) or a ``storage.CSCPattern``; the
    sparse form keeps scheduling O(nnz(L+U)) for the packed storage path.
    ``n_bins``: pack_panels bin count for within-level grouping (clamped to
    the panel count so small problems don't over-provision).
    """
    if not isinstance(pattern, CSCPattern):
        pattern = CSCPattern.from_dense(pattern)
    n = pattern.n
    supernodes = _validate_supernodes(supernodes, n)
    k = len(supernodes)

    sup_of_col = np.repeat(np.arange(k, dtype=np.int64),
                           supernodes[:, 1] - supernodes[:, 0])
    col_counts = pattern.below_diag_counts()

    ancestors: List[np.ndarray] = []
    level = np.zeros(k, dtype=np.int64)
    for j, (s, e) in enumerate(supernodes):
        seg = pattern.rowind[pattern.indptr[s]:pattern.indptr[e]]
        anc = np.unique(sup_of_col[seg[seg < s]])
        ancestors.append(anc)
        level[j] = level[anc].max() + 1 if len(anc) else 0

    partition = pack_panels(supernodes, col_counts,
                            max(1, min(n_bins, k)) if k else max(0, n_bins),
                            policy=policy)

    levels: List[np.ndarray] = []
    for lv in range(int(level.max()) + 1 if k else 0):
        members = np.flatnonzero(level == lv)
        # group by pack_panels bin (batch/placement unit), stable within bin
        order = np.lexsort((members, partition.assignment[members]))
        levels.append(members[order])

    return PanelSchedule(supernodes=supernodes, ancestors=ancestors,
                         level=level, levels=levels, partition=partition,
                         col_counts=col_counts)
