"""Lowering: MatchJob task rectangles → MXU-aligned tile catalogs.

``lower(job)`` is the single tiling implementation behind every
strategy (formerly six near-identical ``catalog_for_*`` builders): each
task's [a0, a0+alen) × [b0, b0+blen) window is intersected with the
aligned (block_m, block_n) grid, tiles that cannot contain a live cell
(entirely on/below the diagonal for triangular tasks, entirely above
the band) are pruned, and every surviving tile carries the task's
predicate scalars verbatim — the catalog column layout is owned by
``kernels.pair_sim`` (NCOLS = 13).

Memory: the catalog is O(#tasks + planned_pairs / (bm·bn)), never
O(P) host-side pair indices.

This module also owns the one-and-only pair-enumeration oracle
(``enumerate_catalog_pairs`` / ``enumerate_task_pairs``) — the
triangular/rect logic the reference executor and the coverage tests
share (formerly duplicated between ``er/pipeline._tile_pairs`` and
``er/executor.enumerate_catalog_pairs``).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from .ir import (A_TILE, B_TILE, R0, R1, C0, C1, TRI, LB_R, LB_C, UB_R,
                 UB_C, BAND, RED, NCOLS, RED_FREE, TASK_NCOLS,
                 T_A0, T_ALEN, T_B0, T_BLEN, T_TRI, T_LB_R, T_LB_C,
                 T_UB_R, T_UB_C, T_BAND, T_RED, MatchJob, TileCatalog)

__all__ = [
    "lower",
    "pad_tiles",
    "pad_catalog",
    "enumerate_task_pairs",
    "enumerate_catalog_pairs",
]


def _rank(counts: np.ndarray) -> np.ndarray:
    """0, 1, …, counts[g] − 1 for each group g in turn: each element's
    place in its group of ``np.repeat(…, counts)``."""
    return np.arange(int(counts.sum())) - np.repeat(
        np.cumsum(counts) - counts, counts)


def lower(job: MatchJob, block_m: int = 128,
          block_n: int = 128) -> TileCatalog:
    """Tile a MatchJob into an MXU tile catalog, in array passes over
    the task table.

    Each task's [a0, a0+alen) × [b0, b0+blen) window is intersected with
    the aligned grid, tiles listed task by task and, within a task, row
    by row. Validity windows/cuts are global-row predicates, so every
    tile of a task carries the same scalars; triangular tasks drop tiles
    entirely on/below the diagonal (no row < col cell), banded tasks
    additionally drop tiles entirely above the col − row < band diagonal
    — the tile set hugs the band instead of filling the bounding
    rectangle — and list their tiles diagonal by diagonal, nearest
    first.

    Tiles inherit their task's reducer attribution; tasks marked
    :data:`ir.RED_FREE` (no planner attribution, e.g. the match_⊥ cross
    job) get their tiles spread round-robin over the job's r reducers —
    the cost-LPT scheduler re-places everything anyway, this only keeps
    the unscheduled catalog balanced for the legacy/round-robin paths.
    """
    bm, bn = block_m, block_n
    t = job.tasks.astype(np.int64).reshape(-1, TASK_NCOLS)
    a0, alen, b0, blen = t[:, T_A0], t[:, T_ALEN], t[:, T_B0], t[:, T_BLEN]
    a1, b1 = a0 + alen, b0 + blen
    i0 = a0 // bm
    n_rows = np.where((alen > 0) & (blen > 0), -(-a1 // bm) - i0, 0)
    # One entry per (task, tile row i): the row's clipped cell rows
    # [ra, rb) and its kept tile columns [jl, jh]. Both predicates are
    # monotone in the column, so each row keeps one run of tiles.
    rt = np.repeat(np.arange(t.shape[0]), n_rows)
    i = i0[rt] + _rank(n_rows)
    ra = np.maximum(i * bm, a0[rt])
    rb = np.minimum((i + 1) * bm, a1[rt])
    jl = b0[rt] // bn
    jh = -(-b1[rt] // bn) - 1
    # Triangular: some cell with row < col — min((j+1)·bn, b1) > ra.
    tri = t[rt, T_TRI] != 0
    jl = np.where(tri, np.maximum(jl, ra // bn), jl)
    jh = np.where(tri & (b1[rt] <= ra), jl - 1, jh)
    # Banded: some cell with col − row < band — the min over the
    # tile∩window of (col − row) is clipped_col_start − (rb − 1), so
    # max(j·bn, b0) < rb + band − 1.
    band = t[rt, T_BAND]
    reach = rb + band - 1
    jh = np.where(band > 0, np.minimum(jh, (reach - 1) // bn), jh)
    jh = np.where((band > 0) & (b0[rt] >= reach), jl - 1, jh)
    n_cols = np.maximum(jh - jl + 1, 0)
    task = np.repeat(rt, n_cols)
    tii = np.repeat(i, n_cols)
    tjj = np.repeat(jl, n_cols) + _rank(n_cols)
    banded = t[task, T_BAND] > 0
    if banded.any():
        # Diagonal tiles first: they hold the nearest sort neighbours,
        # the band's densest survivors, so the stage-1 launches whose
        # tiles overflow the compact capacity (and re-score through the
        # mask path) are the first ones, not every one.
        order = np.lexsort((tii, np.where(banded, tjj - tii, 0), task))
        task, tii, tjj = task[order], tii[order], tjj[order]
    scalars = np.stack([
        a0, a1, b0, b1, t[:, T_TRI] != 0,
        t[:, T_LB_R], t[:, T_LB_C], t[:, T_UB_R], t[:, T_UB_C],
        t[:, T_BAND], t[:, T_RED]], axis=1).astype(np.int32)
    tiles = np.empty((task.size, NCOLS), np.int32)
    tiles[:, A_TILE] = tii
    tiles[:, B_TILE] = tjj
    tiles[:, R0:] = scalars[task]
    free = tiles[:, RED] == RED_FREE
    if free.any():
        tiles[free, RED] = (np.arange(int(free.sum()), dtype=np.int32)
                            % max(job.r, 1))
    return TileCatalog(tiles=tiles, block_m=block_m, block_n=block_n,
                       n_rows_a=job.n_rows_a, n_rows_b=job.n_rows_b,
                       r=max(job.r, 1), total_pairs=job.total_pairs)


# ---------------------------------------------------------------------------
# Shape padding (the serving path's fixed-shape contract)
# ---------------------------------------------------------------------------

def pad_tiles(tiles: np.ndarray, multiple: int) -> np.ndarray:
    """Pad a tile table's second-to-last axis UP to a multiple of
    ``multiple`` rows (>= one full chunk) with all-zero entries — an
    empty validity window r0 == r1 == 0 masks everything out, so padding
    never changes survivors. Works on a flat (T, NCOLS) catalog and on
    per-device (n_dev, cap, NCOLS) shards alike; this is the one padding
    helper behind the former ``pad_catalog_tiles`` / ``_pad_tile_chunks``
    / ``pad_device_tiles`` trio."""
    t = tiles.shape[-2]
    padded = max(multiple, -(-t // multiple) * multiple)
    if padded == t:
        return tiles
    pad_shape = tiles.shape[:-2] + (padded - t, NCOLS)
    return np.concatenate(
        [tiles, np.zeros(pad_shape, np.int32)], axis=-2)


def pad_catalog(catalog: TileCatalog, multiple: int) -> TileCatalog:
    """Pad a catalog's tile table to a multiple of ``multiple`` rows, so
    a chunked scorer sees only one chunk shape — the shape-bucketing the
    serving path relies on for zero steady-state recompiles."""
    tiles = pad_tiles(catalog.tiles, multiple)
    if tiles is catalog.tiles:
        return catalog
    return TileCatalog(tiles=tiles, block_m=catalog.block_m,
                       block_n=catalog.block_n, n_rows_a=catalog.n_rows_a,
                       n_rows_b=catalog.n_rows_b, r=catalog.r,
                       total_pairs=catalog.total_pairs)


# ---------------------------------------------------------------------------
# Pair-enumeration oracle (tests + the reference executor)
# ---------------------------------------------------------------------------

def enumerate_task_pairs(a0: int, alen: int, b0: int, blen: int,
                         tri: bool) -> Tuple[np.ndarray, np.ndarray]:
    """Row-index pairs of one plain match task (no cuts/band) — the
    reference executor's O(pairs) materialization; the catalog path never
    calls this."""
    if tri:
        x, y = np.triu_indices(alen, k=1)
        return a0 + x, a0 + y
    x, y = np.meshgrid(np.arange(alen), np.arange(blen), indexing="ij")
    return a0 + x.ravel(), b0 + y.ravel()


def enumerate_catalog_pairs(catalog: TileCatalog
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """Materialize every pair a catalog covers (numpy, O(P) — tests only).

    Applies the exact kernel predicate per tile; the parity tests assert
    this equals the plan's own pair enumeration, i.e. the catalog covers
    each planned pair exactly once.
    """
    bm, bn = catalog.block_m, catalog.block_n
    gi = np.arange(bm)[:, None]
    gj = np.arange(bn)[None, :]
    out_a, out_b = [], []
    for e in catalog.tiles:
        rows = e[A_TILE].astype(np.int64) * bm + gi
        cols = e[B_TILE].astype(np.int64) * bn + gj
        keep = (rows >= e[R0]) & (rows < e[R1]) & (cols >= e[C0]) & (cols < e[C1])
        if e[TRI]:
            keep &= rows < cols
        keep &= (rows > e[LB_R]) | (cols >= e[LB_C])
        keep &= (rows < e[UB_R]) | (cols <= e[UB_C])
        if e[BAND]:
            keep &= cols - rows < e[BAND]
        ii, jj = np.nonzero(keep)
        out_a.append(rows[ii, 0])
        out_b.append(cols[0, jj])
    if not out_a:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(out_a), np.concatenate(out_b)
