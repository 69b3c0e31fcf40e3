"""Per-range scalar walk over PairRange's (range, block) segments.

The straightforward form of ``core.pair_range.range_segments`` and of its
readers: for each range k, each block its pair-index interval touches,
two scalar ``invert_cell_index`` calls and one tuple. The vectorized
table, gather intervals and task table must equal what this gives, entry
for entry. ``EDGE_CASES`` are BDMs whose segments sit on the corners of
the enumeration.
"""
import numpy as np

from repro.core import enumeration as en
from repro.er.compiler.ir import make_job, task_row


def scalar_segments(plan, k):
    """Range k's segments: [(block, x_lo, y_lo, x_hi, y_hi)]."""
    lo, hi = map(int, plan.bounds[k])
    if hi <= lo:
        return []
    sizes, offsets = plan.block_sizes, plan.offsets
    b_lo, _, _ = en.invert_pair_index(np.int64(lo), sizes, offsets)
    b_hi, _, _ = en.invert_pair_index(np.int64(hi - 1), sizes, offsets)
    out = []
    for blk in range(int(b_lo), int(b_hi) + 1):
        n = int(sizes[blk])
        npairs = int(plan.pair_counts[blk])
        if npairs == 0:
            continue
        qlo = max(lo - int(offsets[blk]), 0)
        qhi = min(hi - int(offsets[blk]), npairs) - 1
        if qhi < qlo:
            continue
        x_lo, y_lo = (int(v) for v in en.invert_cell_index(np.int64(qlo), n))
        x_hi, y_hi = (int(v) for v in en.invert_cell_index(np.int64(qhi), n))
        out.append((blk, x_lo, y_lo, x_hi, y_hi))
    return out


def scalar_table(plan):
    """Every range's segments as (S, 6) rows ``k, blk, x_lo, y_lo, x_hi,
    y_hi``, by range then block."""
    rows = [(k,) + seg for k in range(plan.r) for seg in scalar_segments(plan, k)]
    return np.asarray(rows, np.int64).reshape(-1, 6)


def scalar_intervals(plan, k):
    """Range k's gather intervals (<= 2 a block) in blocked-layout rows."""
    out = []
    for blk, x_lo, y_lo, x_hi, y_hi in scalar_segments(plan, k):
        n = int(plan.block_sizes[blk])
        if x_hi >= x_lo + 2:
            ivs = [(x_lo, n - 1)]
        elif x_hi == x_lo:
            if y_lo == x_lo + 1:
                ivs = [(x_lo, y_hi)]
            else:
                ivs = [(x_lo, x_lo), (y_lo, y_hi)]
        else:
            first, second = (x_lo, y_hi), (y_lo, n - 1)
            if second[0] <= first[1] + 1:
                ivs = [(x_lo, n - 1)]
            else:
                ivs = [first, second]
        base = int(plan.estart[blk])
        out.append((blk, [(base + a, base + b) for a, b in ivs]))
    return out


def scalar_job(plan):
    """The MatchJob built one ``task_row`` per segment."""
    rows = []
    for k in range(plan.r):
        for blk, x_lo, y_lo, x_hi, y_hi in scalar_segments(plan, k):
            e0 = int(plan.estart[blk])
            n = int(plan.block_sizes[blk])
            c0 = e0 + (y_lo if x_hi == x_lo else x_lo + 1)
            c1 = e0 + (y_hi + 1 if x_hi == x_lo else n)
            rows.append(task_row(
                e0 + x_lo, x_hi - x_lo + 1, c0, c1 - c0, True, k,
                lb=(e0 + x_lo, e0 + y_lo), ub=(e0 + x_hi, e0 + y_hi)))
    n_rows = int(plan.block_sizes.sum())
    return make_job(rows, n_rows, n_rows, plan.r, plan.total_pairs)


def _bdm(*sizes):
    """A two-partition BDM with the given block sizes."""
    s = np.asarray(sizes, np.int64)
    return np.stack([s // 2, s - s // 2], axis=1)


# name -> (bdm, r)
EDGE_CASES = {
    # Every block a singleton (or empty): P = 0, every range empty.
    "all_singletons": (_bdm(1, 1, 0, 1, 1), 3),
    # r > P: the ceil split leaves the last ranges empty.
    "r_above_pairs": (_bdm(3, 2, 1), 9),
    # One block holds every pair, among singletons and empty blocks.
    "one_block_all_pairs": (_bdm(1, 0, 40, 1, 1), 7),
    # Pair counts 6, 6, 6 and 18 / 3 = 6: range bounds on block bounds.
    "bounds_on_block_bounds": (_bdm(4, 4, 4), 3),
    # Zero-pair blocks between paired ones, ranges spanning them.
    "zero_pair_blocks_between": (_bdm(5, 1, 0, 1, 6, 1, 0, 3, 1), 4),
    # Range [3, 6) of a 5-block runs (0, 4)..(1, 3): the first column's
    # y-tail [4, 4] touches [0, 3], so the two intervals merge.
    "two_columns_touching": (_bdm(5), 4),
}
