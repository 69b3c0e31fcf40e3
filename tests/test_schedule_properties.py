"""Hypothesis leg of the compiler/scheduler invariants.

Fuzzes the SAME ``check_*`` functions as the deterministic leg
(``test_compiler_schedule.py``) over hypothesis-drawn strategy
instances: (a) coverage/disjointness is preserved under any schedule,
(b) cost-LPT makespan beats round-robin on dominant-block skew and
never loses more than a tile quantum elsewhere, plus the exactness of
the tile cost model everything rests on.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep — skip, don't kill collection
from hypothesis import given, settings, strategies as st

from repro.core import (plan_basic, plan_block_split, plan_pair_range,
                        plan_sorted_neighborhood)
from repro.core.two_source import TwoSourceBDM, plan_pair_range_2src
from repro.er.compiler import (cross_job, lower, plan_to_job,
                               row_sum_tiles, tile_costs)
from repro.er.compiler.ir import (A_TILE, B_TILE, BAND, C0, C1, LB_C, LB_R,
                                  NCOLS, NO_LB, NO_UB, R0, R1, UB_C, UB_R,
                                  TileCatalog)

import lower_oracle

from test_compiler_schedule import (check_lpt_beats_round_robin,
                                    check_lpt_within_tile_quantum,
                                    check_schedule_preserves_coverage,
                                    check_tile_costs_exact)


@st.composite
def bdm_strategy(draw):
    """Small BDMs with empty blocks, singletons and a possible heavy hitter."""
    b = draw(st.integers(1, 10))
    m = draw(st.integers(1, 4))
    rows = [[draw(st.integers(0, 12)) for _ in range(m)] for _ in range(b)]
    if draw(st.booleans()):
        rows[draw(st.integers(0, b - 1))] = [draw(st.integers(20, 50))] * m
    return np.asarray(rows, np.int64)


@st.composite
def any_catalog(draw):
    """A lowered catalog from a random strategy over a random instance."""
    kind = draw(st.sampled_from(
        ["basic", "block_split", "pair_range", "sn", "2src", "cross"]))
    r = draw(st.integers(1, 6))
    bm = draw(st.sampled_from([16, 32]))
    if kind == "sn":
        plan = plan_sorted_neighborhood(draw(st.integers(2, 200)),
                                        draw(st.integers(2, 30)), r)
        return lower(plan_to_job(plan), bm, bm)
    if kind == "cross":
        return lower(cross_job(draw(st.integers(1, 80)),
                               draw(st.integers(1, 40)), r), bm, bm)
    if kind == "2src":
        ra, rb = draw(bdm_strategy()), draw(bdm_strategy())
        b = min(ra.shape[0], rb.shape[0])
        bdm2 = TwoSourceBDM(bdm_r=ra[:b], bdm_s=rb[:b])
        return lower(plan_to_job(plan_pair_range_2src(bdm2, r)), bm, bm)
    plan = {"basic": plan_basic, "block_split": plan_block_split,
            "pair_range": plan_pair_range}[kind](draw(bdm_strategy()), r)
    return lower(plan_to_job(plan), bm, bm)


@st.composite
def dominant_block_bdm(draw):
    """The paper's skew regime: one block ≫ everything else, spanning
    many catalog tiles (so tile-level LPT has room to spread it)."""
    b = draw(st.integers(3, 12))
    m = draw(st.integers(1, 4))
    rows = [[draw(st.integers(0, 6)) for _ in range(m)] for _ in range(b)]
    big = draw(st.integers(128, 300))
    rows[draw(st.integers(0, b - 1))] = [big // m + (i < big % m)
                                         for i in range(m)]
    return np.asarray(rows, np.int64)


@given(any_catalog())
@settings(max_examples=40, deadline=None)
def test_tile_costs_exact(cat):
    check_tile_costs_exact(cat)


@st.composite
def raw_tiles(draw):
    """Catalog rows straight from the predicate vocabulary: windows that
    miss, clip or cover their tile, with or without tri, each lb/ub cut
    absent, dead or live, and a band or none."""
    bm, bn = draw(st.sampled_from([(8, 8), (8, 16), (16, 8)]))
    rows = []
    for _ in range(draw(st.integers(1, 24))):
        i, j = draw(st.integers(0, 4)), draw(st.integers(0, 4))
        r_at = st.integers(i * bm - bm // 2, (i + 1) * bm + bm // 2)
        c_at = st.integers(j * bn - bn // 2, (j + 1) * bn + bn // 2)
        lb = draw(st.one_of(st.just((NO_LB, NO_LB)), st.tuples(r_at, c_at)))
        ub = draw(st.one_of(st.just((NO_UB, NO_UB)), st.tuples(r_at, c_at)))
        rows.append([i, j, draw(r_at), draw(r_at), draw(c_at), draw(c_at),
                     draw(st.integers(0, 1)), *lb, *ub,
                     draw(st.one_of(st.just(0), st.integers(1, 3 * bn))),
                     0])
    tiles = np.asarray(rows, np.int32).reshape(-1, NCOLS)
    return TileCatalog(tiles=tiles, block_m=bm, block_n=bn, n_rows_a=0,
                       n_rows_b=0, r=1, total_pairs=0)


def _cuts_a_cell(cat):
    """Per tile, from its cells: a band, or an lb/ub cut that drops some
    cell of the tile's validity window."""
    bm, bn = cat.block_m, cat.block_n
    out = []
    for e in cat.tiles.astype(np.int64):
        rows = e[A_TILE] * bm + np.arange(bm)[:, None]
        cols = e[B_TILE] * bn + np.arange(bn)[None, :]
        window = ((rows >= e[R0]) & (rows < e[R1])
                  & (cols >= e[C0]) & (cols < e[C1]))
        lb_drops = (rows <= e[LB_R]) & (cols < e[LB_C])
        ub_drops = (rows >= e[UB_R]) & (cols > e[UB_C])
        out.append(bool(e[BAND] > 0)
                   or bool((window & (lb_drops | ub_drops)).any()))
    return np.asarray(out, bool)


@given(raw_tiles())
@settings(max_examples=200, deadline=None)
def test_closed_form_tile_costs_match_row_sum_oracle(cat):
    """The closed form, with its row-sum fallback, equals the (T, bm) row
    sum on every tile, and the fallback takes exactly the tiles that a
    band or a live corner cut reaches into."""
    np.testing.assert_array_equal(tile_costs(cat),
                                  lower_oracle.tile_costs(cat))
    np.testing.assert_array_equal(row_sum_tiles(cat), _cuts_a_cell(cat))


@given(any_catalog(), st.integers(1, 8),
       st.sampled_from(["cost_lpt", "round_robin"]))
@settings(max_examples=40, deadline=None)
def test_schedule_preserves_coverage(cat, n_dev, policy):
    check_schedule_preserves_coverage(cat, n_dev, policy)


@given(dominant_block_bdm(), st.integers(4, 16), st.integers(2, 8))
@settings(max_examples=30, deadline=None)
def test_cost_lpt_beats_round_robin_on_skew(bdm, r, n_dev):
    check_lpt_beats_round_robin(bdm, r, n_dev)


@given(any_catalog(), st.integers(2, 8))
@settings(max_examples=30, deadline=None)
def test_cost_lpt_never_worse_than_a_tile_quantum(cat, n_dev):
    check_lpt_within_tile_quantum(cat, n_dev)


@given(any_catalog(), st.integers(1, 5), st.integers(0, 10 ** 6))
@settings(max_examples=40, deadline=None)
def test_calibrated_schedule_preserves_coverage(cat, n_dev, seed):
    """EWMA calibration re-weights placement only: any randomly-trained
    feedback model leaves pair coverage/disjointness and exact live-pair
    load accounting untouched."""
    from test_feedback_scheduling import \
        check_calibrated_schedule_preserves_coverage
    check_calibrated_schedule_preserves_coverage(cat, n_dev, seed)
