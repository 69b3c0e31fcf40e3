"""Per-task and per-tile oracles for lowering and scheduling.

The straightforward forms of ``compiler.lower``, ``schedule.tile_costs``
and ``core.assignment.greedy_lpt``: one ``task_tiles`` call per task,
every tile's live count as a (T, bm) row sum, and one ``np.argmin`` over
the bins per weight. The array-pass versions must equal what these give,
array for array and in the same order.
"""
from typing import Tuple

import numpy as np

from repro.er.compiler.ir import (A_TILE, B_TILE, R0, R1, C0, C1, TRI,
                                  LB_R, LB_C, UB_R, UB_C, BAND, RED, NCOLS,
                                  NO_LB, NO_UB, RED_FREE, T_A0, T_ALEN, T_B0,
                                  T_BLEN, T_TRI, T_LB_R, T_LB_C, T_UB_R,
                                  T_UB_C, T_BAND, T_RED, MatchJob,
                                  TileCatalog)

_COST_SLAB = 65_536


def task_tiles(a0: int, alen: int, b0: int, blen: int, tri: bool,
               reducer: int, bm: int, bn: int,
               lb: Tuple[int, int] = (NO_LB, NO_LB),
               ub: Tuple[int, int] = (NO_UB, NO_UB),
               band: int = 0) -> np.ndarray:
    """Aligned tiles intersecting one task's [a0, a0+alen) × [b0, b0+blen)
    window. Validity windows/cuts are global-row predicates, so every tile
    of a task carries the same scalars; triangular tasks drop tiles
    entirely on/below the diagonal (no row < col cell), banded tasks
    additionally drop tiles entirely above the col − row < band diagonal —
    the tile set hugs the band instead of filling the bounding rectangle
    — and list their tiles diagonal by diagonal, nearest first."""
    if alen <= 0 or blen <= 0:
        return np.zeros((0, NCOLS), np.int32)
    ii = np.arange(a0 // bm, -(-(a0 + alen) // bm), dtype=np.int64)
    jj = np.arange(b0 // bn, -(-(b0 + blen) // bn), dtype=np.int64)
    tii, tjj = np.meshgrid(ii, jj, indexing="ij")
    tii, tjj = tii.ravel(), tjj.ravel()
    if tri:
        keep = np.maximum(tii * bm, a0) < np.minimum((tjj + 1) * bn, b0 + blen)
        tii, tjj = tii[keep], tjj[keep]
    if band > 0:
        # Some cell with col − row < band: min over the tile∩window of
        # (col − row) is clipped_col_start − (clipped_row_end − 1).
        keep = (np.maximum(tjj * bn, b0)
                < np.minimum((tii + 1) * bm, a0 + alen) + band - 1)
        # Diagonal tiles first: they hold the nearest sort neighbours,
        # the band's densest survivors, so the stage-1 launches whose
        # tiles overflow the compact capacity (and re-score through the
        # mask path) are the first ones, not every one.
        order = np.lexsort((tii[keep], tjj[keep] - tii[keep]))
        tii, tjj = tii[keep][order], tjj[keep][order]
    t = np.empty((tii.size, NCOLS), np.int32)
    t[:, A_TILE] = tii
    t[:, B_TILE] = tjj
    t[:, R0] = a0
    t[:, R1] = a0 + alen
    t[:, C0] = b0
    t[:, C1] = b0 + blen
    t[:, TRI] = int(tri)
    t[:, LB_R], t[:, LB_C] = lb
    t[:, UB_R], t[:, UB_C] = ub
    t[:, BAND] = band
    t[:, RED] = reducer
    return t


def lower(job: MatchJob, block_m: int = 128,
          block_n: int = 128) -> TileCatalog:
    """Tile a MatchJob into an MXU tile catalog.

    Tiles inherit their task's reducer attribution; tasks marked
    :data:`ir.RED_FREE` (no planner attribution, e.g. the match_⊥ cross
    job) get their tiles spread round-robin over the job's r reducers —
    the cost-LPT scheduler re-places everything anyway, this only keeps
    the unscheduled catalog balanced for the legacy/round-robin paths.
    """
    parts = []
    for t in job.tasks:
        parts.append(task_tiles(
            int(t[T_A0]), int(t[T_ALEN]), int(t[T_B0]), int(t[T_BLEN]),
            bool(t[T_TRI]), int(t[T_RED]), block_m, block_n,
            lb=(int(t[T_LB_R]), int(t[T_LB_C])),
            ub=(int(t[T_UB_R]), int(t[T_UB_C])),
            band=int(t[T_BAND])))
    tiles = (np.concatenate(parts, axis=0) if parts
             else np.zeros((0, NCOLS), np.int32))
    free = tiles[:, RED] == RED_FREE
    if free.any():
        tiles[free, RED] = (np.arange(int(free.sum()), dtype=np.int32)
                            % max(job.r, 1))
    return TileCatalog(tiles=tiles, block_m=block_m, block_n=block_n,
                       n_rows_a=job.n_rows_a, n_rows_b=job.n_rows_b,
                       r=max(job.r, 1), total_pairs=job.total_pairs)


def tile_costs(catalog: TileCatalog) -> np.ndarray:
    """Exact live-pair count per tile under ALL catalog predicates.

    For a fixed row, every predicate constrains the column to one
    interval: the validity window gives [c0, c1), the tile bounds give
    [b_tile·bn, (b_tile+1)·bn), tri demands col ≥ row+1, the band
    demands col < row+band, the lb cut applies col ≥ lb_c only on rows
    ≤ lb_r, the ub cut applies col ≤ ub_c only on rows ≥ ub_r. The live
    count is Σ_rows max(0, hi − lo) — exact, vectorized, O(T·bm)."""
    tiles = catalog.tiles
    if tiles.shape[0] == 0:
        return np.zeros(0, np.int64)
    bm, bn = catalog.block_m, catalog.block_n
    ar = np.arange(bm, dtype=np.int64)[None, :]
    out = np.empty(tiles.shape[0], np.int64)
    for s in range(0, tiles.shape[0], _COST_SLAB):
        t = tiles[s:s + _COST_SLAB].astype(np.int64)
        rows = t[:, A_TILE, None] * bm + ar
        lo = np.maximum(t[:, C0, None], t[:, B_TILE, None] * bn)
        hi = np.minimum(t[:, C1, None], (t[:, B_TILE, None] + 1) * bn)
        lo = np.where(t[:, TRI, None] != 0, np.maximum(lo, rows + 1), lo)
        hi = np.where(t[:, BAND, None] > 0,
                      np.minimum(hi, rows + t[:, BAND, None]), hi)
        lo = np.where(rows <= t[:, LB_R, None],
                      np.maximum(lo, t[:, LB_C, None]), lo)
        hi = np.where(rows >= t[:, UB_R, None],
                      np.minimum(hi, t[:, UB_C, None] + 1), hi)
        valid = (rows >= t[:, R0, None]) & (rows < t[:, R1, None])
        out[s:s + _COST_SLAB] = (np.maximum(hi - lo, 0) * valid).sum(axis=1)
    return out


def greedy_lpt(weights: np.ndarray, r: int) -> Tuple[np.ndarray, np.ndarray]:
    """Assign each weighted task to one of ``r`` bins, largest-first.

    Returns ``(assignment, loads)`` — assignment[t] in [0, r), loads (r,).
    Ties broken by lowest bin index (paper's getNextReduceTask).
    """
    w = np.asarray(weights, np.int64)
    order = np.argsort(-w, kind="stable")
    assignment = np.empty(w.shape[0], np.int64)
    loads = np.zeros(r, np.int64)
    for t in order:
        k = int(np.argmin(loads))
        assignment[t] = k
        loads[k] += w[t]
    return assignment, loads
