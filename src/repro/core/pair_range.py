"""PairRange (paper §V, Alg. 2).

All P pairs get a global index via the closed-form enumeration
(core/enumeration.py); the index space is cut into r near-equal ranges and
range k *is* reduce task k. Map sends an entity to every range that contains
at least one of its pairs (the exact union, not just the [Rmin, Rmax] span).

TPU mapping: a device owning range [lo, hi) materializes its pair list with
the vectorized inverse ``p -> (block, x, y)`` and gathers the two feature
rows per pair from the blocked layout. The per-(device, block) *gather set*
is provably a union of <= 2 contiguous row intervals (see
:func:`range_block_intervals`), which is what the collective-volume
accounting (Fig. 12 analog: bytes over ICI) and the sharded executor use.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

from . import enumeration as en

__all__ = [
    "PairRangePlan",
    "plan_pair_range",
    "pairs_of_range",
    "pairs_of_range_jnp",
    "range_segments",
    "range_block_segments",
    "range_block_intervals",
    "entity_range_matrix",
    "map_output_size",
]


@dataclass(frozen=True)
class PairRangePlan:
    r: int
    bdm: np.ndarray            # (b, m)
    block_sizes: np.ndarray    # (b,)
    pair_counts: np.ndarray    # (b,)
    offsets: np.ndarray        # (b,) o(i), exclusive cumsum of pair_counts
    estart: np.ndarray         # (b,) entity-row offset per block (blocked layout)
    bounds: np.ndarray         # (r, 2) [lo, hi) pair-index bounds
    total_pairs: int

    @property
    def reducer_pairs(self) -> np.ndarray:
        return (self.bounds[:, 1] - self.bounds[:, 0]).astype(np.int64)


def plan_pair_range(bdm: np.ndarray, r: int) -> PairRangePlan:
    bdm = np.asarray(bdm, np.int64)
    sizes = bdm.sum(axis=1)
    pairs = en.block_pair_counts(sizes)
    offsets, total = en.pair_offsets(pairs)
    estart = np.concatenate([np.zeros(1, np.int64), np.cumsum(sizes)[:-1]])
    bounds = en.range_bounds(total, r)
    return PairRangePlan(
        r=r, bdm=bdm, block_sizes=sizes, pair_counts=pairs,
        offsets=offsets, estart=estart, bounds=bounds, total_pairs=total)


def pairs_of_range(plan: PairRangePlan, k: int):
    """Materialize range k's pairs: (block, x, y, row_a, row_b) int64 arrays."""
    lo, hi = plan.bounds[k]
    p = np.arange(lo, hi, dtype=np.int64)
    block, x, y = en.invert_pair_index(p, plan.block_sizes, plan.offsets)
    return block, x, y, plan.estart[block] + x, plan.estart[block] + y


def pairs_of_range_jnp(sizes, offsets, estart, lo, count: int, total: int):
    """jnp twin with a static pair count (padded past ``total``).

    Returns (row_a, row_b, valid) — padded entries get row 0 and valid=False.
    All inputs are jnp arrays / traced scalars except the static ``count``.
    """
    import jax.numpy as jnp

    idx_dtype = sizes.dtype
    p = lo + jnp.arange(count, dtype=idx_dtype)
    valid = p < total
    pc = jnp.where(valid, p, 0)
    block = jnp.searchsorted(offsets, pc, side="right") - 1
    q = pc - offsets[block]
    n = sizes[block]
    # Float estimate of the triangular root, then integer boundary repair.
    af = (2 * n - 1).astype(jnp.float32)
    disc = jnp.maximum(af * af - 8.0 * q.astype(jnp.float32), 0.0)
    est = (af - jnp.sqrt(disc)) / 2.0
    x = jnp.clip(jnp.floor(est).astype(q.dtype), 0, jnp.maximum(n - 2, 0))
    # 8 repair passes cover float32 estimate error of up to +/-8; the
    # property tests sweep N to verify exactness for the supported sizes.
    for _ in range(8):
        s_x = (x * (2 * n - x - 1)) // 2
        x = jnp.where(s_x > q, x - 1, x)
        s_x1 = ((x + 1) * (2 * n - x - 2)) // 2
        x = jnp.where(s_x1 <= q, x + 1, x)
    x = jnp.clip(x, 0, jnp.maximum(n - 2, 0))
    y = q - (x * (2 * n - x - 1)) // 2 + x + 1
    return estart[block] + x, estart[block] + y, valid


def _segments(plan: PairRangePlan, ks: np.ndarray) -> np.ndarray:
    """Segment table of the ranges ``ks`` (ascending): see
    :func:`range_segments`."""
    lo, hi = plan.bounds[ks, 0], plan.bounds[ks, 1]
    live = hi > lo
    ks, lo, hi = ks[live], lo[live], hi[live]
    if ks.size == 0:
        return np.zeros((0, 6), np.int64)
    offsets = plan.offsets
    # First and last block of each range (the block of pair lo, of hi - 1).
    b_lo = np.searchsorted(offsets, lo, side="right") - 1
    b_hi = np.searchsorted(offsets, hi - 1, side="right") - 1
    # One entry per (range, block) the range touches, by range then block.
    width = b_hi - b_lo + 1
    first = np.cumsum(width) - width
    step = np.arange(int(width.sum()), dtype=np.int64) - np.repeat(first, width)
    blk = np.repeat(b_lo, width) + step
    lo, hi, k = (np.repeat(v, width) for v in (lo, hi, ks))
    npairs = plan.pair_counts[blk]
    qlo = np.maximum(lo - offsets[blk], 0)
    qhi = np.minimum(hi - offsets[blk], npairs) - 1
    keep = (npairs > 0) & (qhi >= qlo)
    k, blk, qlo, qhi = k[keep], blk[keep], qlo[keep], qhi[keep]
    n = plan.block_sizes[blk]
    x_lo, y_lo = en.invert_cell_index(np.concatenate([qlo, qhi]),
                                      np.concatenate([n, n]))
    s = k.size
    return np.stack([k, blk, x_lo[:s], y_lo[:s], x_lo[s:], y_lo[s:]],
                    axis=1).astype(np.int64, copy=False)


def range_segments(plan: PairRangePlan) -> np.ndarray:
    """Every range's per-block pair segments as one (S, 6) int64 table.

    Row ``(k, blk, x_lo, y_lo, x_hi, y_hi)``: range k's pair-index
    interval [lo, hi) intersected with block ``blk`` is a contiguous run
    of cell indices, i.e. (in the column-major triangular enumeration)
    the cells from (x_lo, y_lo) through (x_hi, y_hi) inclusive: a
    prefix-cut first column, full middle columns, a suffix-cut last
    column. This is the O(1)-per-block description the tile-catalog
    executor compiles to corner-cut masks — no per-pair
    materialization. Rows run by range, then by block; only non-empty
    segments appear (empty ranges and zero-pair blocks have none);
    coordinates are block-local. Array operations over the O(r + b)
    segments: no per-range or per-block Python step.
    """
    return _segments(plan, np.arange(plan.r, dtype=np.int64))


def range_block_segments(plan: PairRangePlan, k: int) -> List[Tuple[int, int, int, int, int]]:
    """Range k's rows of :func:`range_segments`, as
    [(block, x_lo, y_lo, x_hi, y_hi)] tuples of ints."""
    segs = _segments(plan, np.array([k], np.int64))
    return [tuple(row) for row in segs[:, 1:].tolist()]


def _gather_intervals(plan: PairRangePlan, segs: np.ndarray):
    """Block-local gather intervals of each segment of ``segs``: the
    first ``(lo1, hi1)``, and ``(lo2, hi2)`` where ``two`` holds.

    The <= 2 bound: within one block a contiguous pair-index interval
    covers columns x_lo..x_hi; if it spans >= 3 columns, some middle
    column is complete, whose y-values reach N-1, collapsing the union
    to a single interval [x_lo, N-1]; otherwise the union is
    [x_lo, ...] plus at most one y-tail, which merges into the first
    interval when they touch.
    """
    _, blk, x_lo, y_lo, x_hi, y_hi = segs.T
    n = plan.block_sizes[blk]
    one_col = x_hi == x_lo
    two_col = x_hi == x_lo + 1
    # One column: [x_lo] ∪ [y_lo, y_hi], one interval if y_lo = x_lo + 1.
    # Two columns: [x_lo, x_lo+1] ∪ [x_hi+1, y_hi] = [x_lo, y_hi], plus the
    # first column's y-tail [y_lo, n-1] unless it touches the first.
    one_tail = one_col & (y_lo != x_lo + 1)
    two_tail = two_col & (y_lo > y_hi + 1)
    hi1 = np.where(one_col, np.where(one_tail, x_lo, y_hi),
                   np.where(two_tail, y_hi, n - 1))
    two = one_tail | two_tail
    hi2 = np.where(one_col, y_hi, n - 1)
    return x_lo, hi1, y_lo, hi2, two


def range_block_intervals(plan: PairRangePlan, k: int) -> List[Tuple[int, List[Tuple[int, int]]]]:
    """Per-block gather intervals (<= 2 each) for range k.

    Returns [(block, [(row_lo, row_hi_inclusive), ...]), ...] in blocked-
    layout rows; :func:`_gather_intervals` gives the bound's proof.
    """
    segs = _segments(plan, np.array([k], np.int64))
    base = plan.estart[segs[:, 1]]
    lo1, hi1, lo2, hi2, two = _gather_intervals(plan, segs)
    rows = np.stack([segs[:, 1], base + lo1, base + hi1, base + lo2,
                     base + hi2, two], axis=1).tolist()
    return [(blk, [(a1, b1), (a2, b2)] if t else [(a1, b1)])
            for blk, a1, b1, a2, b2, t in rows]


def entity_range_matrix(plan: PairRangePlan, max_pairs: int = 50_000_000) -> np.ndarray:
    """Exact (n_entities, r) bool membership — which ranges each entity is
    sent to (the union Alg. 2 computes map-side). Brute-force over all
    pairs, chunked; intended for DS1-scale benchmarks/tests."""
    if plan.total_pairs > max_pairs:
        raise ValueError(f"{plan.total_pairs} pairs exceeds brute-force budget")
    n = int(plan.block_sizes.sum())
    mask = np.zeros((n, plan.r), bool)
    per = -(-plan.total_pairs // plan.r) if plan.total_pairs else 1
    chunk = 4_000_000
    for lo in range(0, plan.total_pairs, chunk):
        p = np.arange(lo, min(lo + chunk, plan.total_pairs), dtype=np.int64)
        blk, x, y = en.invert_pair_index(p, plan.block_sizes, plan.offsets)
        rng = np.minimum(p // per, plan.r - 1)
        mask[plan.estart[blk] + x, rng] = True
        mask[plan.estart[blk] + y, rng] = True
    return mask


def map_output_size(plan: PairRangePlan, segs: np.ndarray | None = None) -> int:
    """kv-pairs emitted by map (Fig. 12): sum over entities of the number
    of relevant ranges, equivalently sum over ranges of the gather-set
    size. Closed form via the <=2-interval bound of
    :func:`_gather_intervals`: array operations over the O(r + b)
    segments of :func:`range_segments` (``segs``, when the caller has
    the table already), never O(P), so it is exact at any scale (DS2's
    6.7·10⁹ pairs included). ``entity_range_matrix`` remains the
    brute-force oracle in tests."""
    if segs is None:
        segs = range_segments(plan)
    lo1, hi1, lo2, hi2, two = _gather_intervals(plan, segs)
    return int((hi1 - lo1 + 1).sum() + np.where(two, hi2 - lo2 + 1, 0).sum())
