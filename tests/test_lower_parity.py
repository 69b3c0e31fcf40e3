"""Array-pass lowering and scheduling against their per-task oracles
(``tests/lower_oracle.py``): the catalog ``lower`` cuts, tile order
included, the tile costs and the LPT assignment and loads, array for
array, for every job shape the planners build and at several tile
geometries of the autotuning lattice."""
import numpy as np
import pytest

import lower_oracle as oracle
from pair_range_oracle import EDGE_CASES
from repro.core import (plan_basic, plan_block_split, plan_pair_range,
                        plan_sorted_neighborhood)
from repro.core.assignment import greedy_lpt
from repro.core.two_source import (TwoSourceBDM, plan_block_split_2src,
                                   plan_pair_range_2src)
from repro.er.compiler import (GEOMETRY_LATTICE, cross_job, lower,
                               plan_to_job, sn_halo_job, tile_costs)
from repro.er.compiler.ir import NO_LB, NO_UB, RED_FREE, make_job, task_row


def _bdm(seed, b=12, m=3, big=700):
    """A small BDM with empty blocks, singletons and one dominant block."""
    rng = np.random.default_rng(seed)
    bdm = rng.integers(0, 40, (b, m)).astype(np.int64)
    bdm[rng.integers(0, b)] = big // m
    bdm[rng.integers(0, b)] = 0
    return bdm


def _two_source(seed):
    return TwoSourceBDM(bdm_r=_bdm(seed, big=300), bdm_s=_bdm(seed + 1, big=200))


def _zero_length():
    """Empty windows, one of them unaligned, between live tasks."""
    return make_job([
        task_row(0, 90, 0, 90, True, 0),
        task_row(37, 0, 50, 20, True, 1),
        task_row(70, 40, 131, 0, False, 2),
        task_row(100, -3, 100, 5, False, 0),
        task_row(200, 60, 300, 70, False, RED_FREE),
        task_row(0, 0, 0, 0, True, 1),
    ], 400, 400, 3, 0)


def _every_predicate(seed, n_tasks=40, n=700):
    """Random windows with every mix of tri, lb/ub corner cuts (live,
    dead or absent), band and RED_FREE."""
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(n_tasks):
        a0, b0 = rng.integers(0, n, 2)
        alen, blen = rng.integers(0, 300, 2)
        lb = ((int(rng.integers(a0 - 5, a0 + alen + 5)),
               int(rng.integers(b0 - 5, b0 + blen + 5)))
              if rng.random() < 0.5 else (NO_LB, NO_LB))
        ub = ((int(rng.integers(a0 - 5, a0 + alen + 5)),
               int(rng.integers(b0 - 5, b0 + blen + 5)))
              if rng.random() < 0.5 else (NO_UB, NO_UB))
        band = int(rng.integers(1, 200)) if rng.random() < 0.3 else 0
        red = RED_FREE if rng.random() < 0.2 else int(rng.integers(0, 6))
        rows.append(task_row(a0, alen, b0, blen, rng.random() < 0.5, red,
                             lb=lb, ub=ub, band=band))
    return make_job(rows, 2 * n, 2 * n, 6, 0)


JOBS = {
    "basic": lambda: plan_to_job(plan_basic(_bdm(1), 4)),
    "block_split": lambda: plan_to_job(plan_block_split(_bdm(2), 5)),
    "pair_range": lambda: plan_to_job(plan_pair_range(_bdm(3), 7)),
    **{f"pair_range.{name}": (lambda bdm=bdm, r=r:
                              plan_to_job(plan_pair_range(bdm, r)))
       for name, (bdm, r) in EDGE_CASES.items()},
    "sn": lambda: plan_to_job(plan_sorted_neighborhood(900, 70, 6)),
    "sn_halo": lambda: sn_halo_job(plan_sorted_neighborhood(900, 70, 4),
                                   4, 225),
    "block_split_2src": lambda: plan_to_job(
        plan_block_split_2src(_two_source(4), 5)),
    "pair_range_2src": lambda: plan_to_job(
        plan_pair_range_2src(_two_source(6), 5)),
    "cross": lambda: cross_job(300, 170, 5),
    "zero_length": _zero_length,
    "every_predicate": lambda: _every_predicate(8),
}
GEOMETRIES = [GEOMETRY_LATTICE[i] for i in (0, 2, 4, 10, 13)]


@pytest.mark.parametrize("geometry", GEOMETRIES,
                         ids=[f"{m}x{n}" for m, n in GEOMETRIES])
@pytest.mark.parametrize("case", sorted(JOBS))
def test_lowering_matches_oracle(case, geometry):
    job = JOBS[case]()
    got, want = lower(job, *geometry), oracle.lower(job, *geometry)
    assert got.tiles.dtype == want.tiles.dtype == np.int32
    np.testing.assert_array_equal(got.tiles, want.tiles)
    assert (got.block_m, got.block_n, got.n_rows_a, got.n_rows_b, got.r,
            got.total_pairs) == (want.block_m, want.block_n, want.n_rows_a,
                                 want.n_rows_b, want.r, want.total_pairs)
    costs = tile_costs(got)
    assert costs.dtype == np.int64
    np.testing.assert_array_equal(costs, oracle.tile_costs(want))
    for x, y in zip(greedy_lpt(costs, got.r),
                    oracle.greedy_lpt(costs, got.r)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(x, y)


LPT_CASES = {
    "all_equal": ([7] * 20, 6),
    "zeros": ([0] * 10, 3),
    "r_1": (np.random.default_rng(1).integers(0, 50, 30), 1),
    "r_above_T": ([5, 1, 9, 1, 0], 12),
    "empty": ([], 4),
    "ties_and_spread": ([8, 8, 3, 3, 3, 1, 1, 0, 8, 2], 3),
    "scaled_int64": (np.random.default_rng(2).integers(0, 2 ** 40, 200), 9),
}


@pytest.mark.parametrize("case", sorted(LPT_CASES))
def test_greedy_lpt_matches_oracle(case):
    weights, r = LPT_CASES[case]
    w = np.asarray(weights, np.int64)
    got, want = greedy_lpt(w, r), oracle.greedy_lpt(w, r)
    for x, y in zip(got, want):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(x, y)
    assert int(got[1].sum()) == int(w.sum())
