"""Two-source plan oracles (paper Appendix I): brute-force R × S pair
enumeration per block vs ``plan_block_split_2src`` /
``plan_pair_range_2src`` — coverage, disjointness, row-mapping, and the
imbalance bounds (Alg. 2's ceil split, list scheduling, and Graham's
LPT factor against the exact optimum) — on hypothesis-generated skewed
BDMs, plus the cross-tile catalog compilers that wire these plans into
the executor.
(Closes the gap where only ``test_two_source_plans_cover`` existed.)
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st
from lpt_oracle import exact_makespan

from repro.core.two_source import (TwoSourceBDM, pairs_of_range_2src,
                                   plan_block_split_2src,
                                   plan_pair_range_2src,
                                   range_block_segments_2src)
from repro.er.executor import (catalog_for_two_source,
                               enumerate_catalog_pairs, pad_catalog_tiles)


@st.composite
def skewed_bdm2(draw):
    """Per-source BDMs over a shared block space, skewed: a few dominant
    blocks, zero-size blocks on either side, uneven partition counts."""
    b = draw(st.integers(1, 12))
    m_r = draw(st.integers(1, 4))
    m_s = draw(st.integers(1, 3))
    rows_r, rows_s = [], []
    for k in range(b):
        shape = draw(st.sampled_from(["zero_r", "zero_s", "small", "big"]))
        big = draw(st.integers(20, 60))
        if shape == "zero_r":
            rows_r.append([0] * m_r)
            rows_s.append([draw(st.integers(0, 6)) for _ in range(m_s)])
        elif shape == "zero_s":
            rows_r.append([draw(st.integers(0, 6)) for _ in range(m_r)])
            rows_s.append([0] * m_s)
        elif shape == "big":
            rows_r.append([big] + [draw(st.integers(0, 4))] * (m_r - 1))
            rows_s.append([draw(st.integers(1, 30))] + [0] * (m_s - 1))
        else:
            rows_r.append([draw(st.integers(0, 4)) for _ in range(m_r)])
            rows_s.append([draw(st.integers(0, 4)) for _ in range(m_s)])
    return TwoSourceBDM(bdm_r=np.asarray(rows_r, np.int64),
                        bdm_s=np.asarray(rows_s, np.int64))


def _brute_pairs(bdm2):
    """All cross-source cells (block, x, y) and their global rows."""
    sr, ss = bdm2.sizes_r, bdm2.sizes_s
    er = np.concatenate([[0], np.cumsum(sr)[:-1]])
    es = np.concatenate([[0], np.cumsum(ss)[:-1]])
    cells, rows = set(), set()
    for k in range(sr.shape[0]):
        for x in range(int(sr[k])):
            for y in range(int(ss[k])):
                cells.add((k, x, y))
                rows.add((int(er[k] + x), int(es[k] + y)))
    return cells, rows, er, es


@given(skewed_bdm2(), st.integers(1, 16))
@settings(max_examples=40, deadline=None)
def test_pair_range_2src_partitions_and_balance(bdm2, r):
    plan = plan_pair_range_2src(bdm2, r)
    cells, rows, _, _ = _brute_pairs(bdm2)
    assert plan.total_pairs == len(cells)
    seen_cells, seen_rows = set(), set()
    for k in range(r):
        blk, x, y, rr, rs = pairs_of_range_2src(plan, k)
        assert rr.shape == (int(plan.reducer_pairs[k]),)
        for t, rt in zip(zip(blk.tolist(), x.tolist(), y.tolist()),
                         zip(rr.tolist(), rs.tolist())):
            assert t not in seen_cells          # disjoint
            seen_cells.add(t)
            seen_rows.add(rt)
    assert seen_cells == cells                  # exhaustive
    assert seen_rows == rows                    # row mapping exact
    # Alg. 2's ceil split: perfectly balanced by construction.
    if plan.total_pairs:
        assert int(plan.reducer_pairs.max()) == -(-plan.total_pairs // r)


@given(skewed_bdm2(), st.integers(1, 16))
@settings(max_examples=40, deadline=None, derandomize=True)
def test_block_split_2src_covers_and_lpt_bound(bdm2, r):
    plan = plan_block_split_2src(bdm2, r)
    cells, rows, er, es = _brute_pairs(bdm2)
    assert plan.total_pairs == len(cells)
    assert int(plan.reducer_pairs.sum()) == len(cells)
    got_rows = set()
    for t in range(plan.task_block.shape[0]):
        a0, al = int(plan.task_a_start[t]), int(plan.task_a_len[t])
        b0, bl = int(plan.task_b_start[t]), int(plan.task_b_len[t])
        assert al * bl == int(plan.task_pairs[t])
        for i in range(al):
            for j in range(bl):
                p = (a0 + i, b0 + j)
                assert p not in got_rows        # disjoint tasks
                got_rows.add(p)
    assert got_rows == rows                     # exhaustive
    # Greedy LPT is a list schedule: r·max_load <= P + (r − 1)·w_max.
    if plan.total_pairs:
        w_max = int(plan.task_pairs.max())
        assert r * int(plan.reducer_pairs.max()) <= \
            plan.total_pairs + (r - 1) * w_max


# (bdm_r, bdm_s, r): small two-source BDMs whose plans have at most 10
# match tasks — whole blocks (the first three give LPT Graham's tight
# family and the [9, 9, 6, 10, 10] instance) and blocks split into
# partition-pair tasks.
GRAHAM_BDMS = [
    ([[3], [3], [2], [2], [2]], [[1]] * 5, 2),
    ([[5], [5], [4], [4], [3], [3], [3]], [[1]] * 7, 3),
    ([[3], [3], [2], [5], [5]], [[3], [3], [3], [2], [2]], 3),
    ([[6, 3], [2, 0], [1, 0]], [[2, 1], [2, 0], [3, 0]], 2),
    ([[10, 5, 0], [0, 3, 1], [2, 2, 2]], [[4, 2], [1, 1], [0, 0]], 4),
    ([[17], [17], [14], [10], [10], [7], [7], [6], [6]], [[1]] * 9, 5),
    ([[7], [13], [4], [5], [2], [4], [7], [3], [2], [1]],
     [[2], [1], [3], [2], [5], [2], [1], [2], [3], [1]], 4),
    ([[4, 3, 2], [1, 1, 0]], [[3, 1], [2, 0]], 2),
]


@pytest.mark.parametrize("bdm_r,bdm_s,r", GRAHAM_BDMS)
def test_block_split_2src_within_graham_of_exact_opt(bdm_r, bdm_s, r):
    """BlockSplit's greedy LPT over its match tasks against the exact
    optimum: 3r·LPT <= (4r − 1)·OPT."""
    plan = plan_block_split_2src(
        TwoSourceBDM(bdm_r=np.asarray(bdm_r, np.int64),
                     bdm_s=np.asarray(bdm_s, np.int64)), r)
    assert 0 < plan.task_pairs.size <= 10
    assert int(plan.task_pairs.sum()) == plan.total_pairs
    lpt = int(plan.reducer_pairs.max())
    opt = exact_makespan(plan.task_pairs.tolist(), r)
    assert opt <= lpt
    assert 3 * r * lpt <= (4 * r - 1) * opt


@given(skewed_bdm2(), st.integers(1, 12))
@settings(max_examples=30, deadline=None)
def test_range_segments_2src_match_materialization(bdm2, r):
    """The O(1)-per-(range, block) segment decomposition enumerates the
    same cells as the per-pair materialization."""
    plan = plan_pair_range_2src(bdm2, r)
    for k in range(r):
        blk, x, y, _, _ = pairs_of_range_2src(plan, k)
        want = set(zip(blk.tolist(), x.tolist(), y.tolist()))
        got = set()
        for sblk, x_lo, y_lo, x_hi, y_hi in range_block_segments_2src(plan, k):
            ns = int(plan.sizes_s[sblk])
            for q in range(x_lo * ns + y_lo, x_hi * ns + y_hi + 1):
                cell = (sblk, q // ns, q % ns)
                assert cell not in got
                got.add(cell)
        assert got == want


@pytest.mark.parametrize("planner", (plan_pair_range_2src,
                                     plan_block_split_2src))
@pytest.mark.parametrize("bm,bn", [(16, 16), (16, 32)])
def test_two_source_catalog_covers_plan_exactly(planner, bm, bn):
    """Every planned R × S pair appears in the cross-tile catalog exactly
    once — unaligned strips, zero blocks, dominant blocks; padding with
    zero entries adds nothing."""
    rng = np.random.default_rng(9)
    for _ in range(8):
        b = int(rng.integers(1, 9))
        bdm2 = TwoSourceBDM(
            bdm_r=rng.integers(0, 40, (b, int(rng.integers(1, 4)))),
            bdm_s=rng.integers(0, 25, (b, int(rng.integers(1, 3)))))
        if b > 1:
            bdm2.bdm_r[int(rng.integers(0, b))] = 0
        plan = planner(bdm2, int(rng.integers(1, 7)))
        cat = pad_catalog_tiles(catalog_for_two_source(plan, bm, bn), 32)
        assert cat.tiles.shape[0] % 32 == 0
        ea, eb = enumerate_catalog_pairs(cat)
        got = list(zip(ea.tolist(), eb.tolist()))
        assert len(got) == len(set(got))
        _, rows, _, _ = _brute_pairs(bdm2)
        assert set(got) == rows
        assert cat.total_pairs == len(rows)
        assert cat.n_rows_a == int(bdm2.sizes_r.sum())
        assert cat.n_rows_b == int(bdm2.sizes_s.sum())


def test_pair_range_2src_catalog_respects_ranges():
    """Each reducer's tiles cover exactly its own range's cells."""
    rng = np.random.default_rng(4)
    bdm2 = TwoSourceBDM(bdm_r=rng.integers(0, 30, (7, 2)),
                        bdm_s=rng.integers(0, 20, (7, 2)))
    plan = plan_pair_range_2src(bdm2, 5)
    from repro.er.executor import RED, TileCatalog
    cat = catalog_for_two_source(plan, 16, 16)
    for k in range(plan.r):
        sub = cat.tiles[cat.tiles[:, RED] == k]
        ea, eb = enumerate_catalog_pairs(TileCatalog(
            tiles=sub, block_m=16, block_n=16, n_rows_a=cat.n_rows_a,
            n_rows_b=cat.n_rows_b, r=plan.r, total_pairs=0))
        _, _, _, rr, rs = pairs_of_range_2src(plan, k)
        assert set(zip(ea.tolist(), eb.tolist())) == \
            set(zip(rr.tolist(), rs.tolist()))
