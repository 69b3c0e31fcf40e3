"""PairRange's vectorized (range, block) segment table against the
per-range scalar walk (``tests/pair_range_oracle.py``): the table, its
per-range views, the gather intervals, the map-output size, the task
table ``plan_to_job`` builds from it and the tiles ``lower`` cuts, entry
for entry, on Hypothesis-drawn BDMs and on the edge cases."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep — skip, don't kill collection
from hypothesis import given, settings, strategies as st

from pair_range_oracle import (EDGE_CASES, scalar_intervals, scalar_job,
                               scalar_segments, scalar_table)
from repro.core import plan_pair_range
from repro.core.pair_range import (map_output_size, range_block_intervals,
                                   range_block_segments, range_segments)
from repro.er.compiler import lower, plan_to_job

bdm_strategy = st.integers(1, 3).flatmap(lambda m: st.lists(
    st.lists(st.integers(0, 12), min_size=m, max_size=m),
    min_size=1, max_size=25))
CASES = pytest.mark.parametrize("case", sorted(EDGE_CASES))


def _check_segments(plan):
    segs = range_segments(plan)
    assert segs.dtype == np.int64 and segs.shape[1] == 6
    np.testing.assert_array_equal(segs, scalar_table(plan))
    for k in range(plan.r):
        assert range_block_segments(plan, k) == scalar_segments(plan, k)
        assert range_block_intervals(plan, k) == scalar_intervals(plan, k)
    want = sum(hi - lo + 1 for k in range(plan.r)
               for _, ivs in scalar_intervals(plan, k) for lo, hi in ivs)
    assert map_output_size(plan) == want
    assert map_output_size(plan, segs) == want


def _check_job(plan):
    job, want = plan_to_job(plan), scalar_job(plan)
    assert job.tasks.dtype == want.tasks.dtype
    np.testing.assert_array_equal(job.tasks, want.tasks)
    assert (job.n_rows_a, job.n_rows_b, job.r, job.total_pairs) == (
        want.n_rows_a, want.n_rows_b, want.r, want.total_pairs)
    for bm, bn in ((128, 128), (8, 16)):
        np.testing.assert_array_equal(lower(job, bm, bn).tiles,
                                      lower(want, bm, bn).tiles)


@CASES
def test_range_segments_edge_cases(case):
    bdm, r = EDGE_CASES[case]
    _check_segments(plan_pair_range(bdm, r))


@given(bdm_strategy, st.integers(1, 40))
@settings(max_examples=80, deadline=None)
def test_range_segments_equal_scalar_walk(bdm, r):
    _check_segments(plan_pair_range(np.asarray(bdm, np.int64), r))


@CASES
def test_job_tasks_and_tiles_edge_cases(case):
    bdm, r = EDGE_CASES[case]
    _check_job(plan_pair_range(bdm, r))


@given(bdm_strategy, st.integers(1, 40))
@settings(max_examples=40, deadline=None)
def test_job_tasks_and_tiles_equal_scalar_walk(bdm, r):
    _check_job(plan_pair_range(np.asarray(bdm, np.int64), r))


def test_edge_cases_hit_their_corners():
    """Each edge case has the shape its name promises."""
    segs = {c: range_segments(plan_pair_range(*EDGE_CASES[c]))
            for c in EDGE_CASES}
    assert segs["all_singletons"].shape == (0, 6)
    plan = plan_pair_range(*EDGE_CASES["r_above_pairs"])
    assert plan.total_pairs < plan.r
    assert set(segs["r_above_pairs"][:, 0]) < set(range(plan.r))
    assert set(segs["one_block_all_pairs"][:, 1]) == {2}
    plan = plan_pair_range(*EDGE_CASES["bounds_on_block_bounds"])
    np.testing.assert_array_equal(plan.bounds[:, 0], plan.offsets)
    np.testing.assert_array_equal(segs["bounds_on_block_bounds"][:, :2],
                                  [[0, 0], [1, 1], [2, 2]])
    ks = segs["zero_pair_blocks_between"][:, 0]
    assert (np.bincount(ks) > 1).any()   # some range spans several blocks
    np.testing.assert_array_equal(segs["two_columns_touching"][1],
                                  [1, 0, 0, 4, 1, 3])


def test_run_er_spans_count_segments_and_tasks(monkeypatch):
    """``er.plan`` carries the table's row count as ``segments`` and
    ``er.job`` the task count as ``tasks``: one task per segment."""
    from repro.er import ERConfig, make_products, pipeline, run_er

    seen = {}

    class Recorder:
        def __init__(self, name, **counts):
            self.name, self.counts = name, dict(counts)
            seen[name] = self.counts

        def set_metadata(self, **counts):
            self.counts.update(counts)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(pipeline, "span", Recorder)
    ds = make_products(400, seed=3)
    res = run_er(ds.titles, ERConfig(strategy="pair_range", r=7, m=3))
    assert seen["plan"]["segments"] == seen["job"]["tasks"] > 0
    assert seen["lower"]["tiles"] == res.extra["catalog_tiles"]
