"""Property-based tests (hypothesis) on the paper's enumeration math —
the invariants everything else rests on:

  * cell_index / invert_cell_index are mutually inverse bijections onto
    [0, N(N-1)/2);
  * global pair_index bijects onto [0, P) across arbitrary block-size
    vectors (incl. 0- and 1-entity blocks);
  * PairRange ranges partition the pair space with the ceil split of
    Alg. 2 (first r-1 ranges ⌈P/r⌉ pairs);
  * greedy LPT conserves total work, keeps the list-scheduling bound
    r·max_load <= P + (r − 1)·w_max, and stays within Graham's
    (4/3 − 1/3r)·OPT of the exact optimum on fixed small instances;
  * BlockSplit match tasks cover each split block's pair set exactly
    once (disjoint ∪ exhaustive);
  * the jnp closed-form inverse equals the numpy oracle for every p;
  * the Sorted Neighborhood band enumeration bijects onto [0, P), its
    range partition covers every band pair exactly once, and the O(r)
    closed-form map_output_size equals the brute-force gather count.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep — skip, don't kill collection
from hypothesis import given, settings, strategies as st
from lpt_oracle import exact_makespan

from repro.core import enumeration as en
from repro.core import sorted_neighborhood as sn
from repro.core.assignment import greedy_lpt
from repro.core import (compute_bdm, entity_indices, plan_block_split,
                        plan_pair_range, pairs_of_range, update_bdm)
from repro.core.pair_range import pairs_of_range_jnp

sizes_strategy = st.lists(st.integers(0, 60), min_size=1, max_size=30)


@given(st.integers(2, 512))
@settings(max_examples=40, deadline=None)
def test_cell_index_bijection(n):
    q = np.arange(n * (n - 1) // 2, dtype=np.int64)
    x, y = en.invert_cell_index(q, np.int64(n))
    assert (0 <= x).all() and (x < y).all() and (y < n).all()
    np.testing.assert_array_equal(en.cell_index(x, y, n), q)


@given(sizes_strategy)
@settings(max_examples=60, deadline=None)
def test_pair_index_bijection_across_blocks(sizes):
    sizes = np.asarray(sizes, np.int64)
    counts = en.block_pair_counts(sizes)
    offsets, total = en.pair_offsets(counts)
    if total == 0:
        return
    p = np.arange(total, dtype=np.int64)
    blk, x, y = en.invert_pair_index(p, sizes, offsets)
    assert (x < y).all()
    assert (y < sizes[blk]).all()
    np.testing.assert_array_equal(en.pair_index(blk, x, y, sizes, offsets), p)


@given(sizes_strategy, st.integers(1, 40))
@settings(max_examples=60, deadline=None)
def test_range_bounds_partition(sizes, r):
    sizes = np.asarray(sizes, np.int64)
    _, total = en.pair_offsets(en.block_pair_counts(sizes))
    bounds = en.range_bounds(total, r)
    assert bounds.shape == (r, 2)
    assert bounds[0, 0] == 0
    assert bounds[-1, 1] == total
    # contiguity + ceil split (paper Alg. 2)
    per = -(-total // r) if total else 0
    for k in range(r - 1):
        assert bounds[k, 1] == bounds[k + 1, 0]
        assert bounds[k, 1] - bounds[k, 0] in (per, max(total - k * per, 0))


@given(st.lists(st.integers(0, 1000), min_size=1, max_size=200),
       st.integers(1, 16))
@settings(max_examples=60, deadline=None, derandomize=True)
def test_greedy_lpt_bound_and_conservation(weights, r):
    w = np.asarray(weights, np.int64)
    assignment, loads = greedy_lpt(w, r)
    assert loads.sum() == w.sum()
    np.testing.assert_array_equal(
        np.bincount(assignment, weights=w, minlength=r).astype(np.int64), loads)
    # List scheduling: the task that ends last started no later than the
    # average of the other work, so r·max_load <= P + (r − 1)·w_max.
    assert r * int(loads.max()) <= int(w.sum()) + (r - 1) * int(w.max())


# (r, weights, tight): Graham's bound holds with equality on his tight
# family (2r + 1 tasks 2r−1, 2r−1, …, r+1, r+1, r, r, r); r = 3 with
# [9, 9, 6, 10, 10] has LPT = OPT = 18 above (4/3 − 1/9)·max(P/r, w_max).
GRAHAM_CASES = [
    (2, [3, 3, 2, 2, 2], True),
    (3, [5, 5, 4, 4, 3, 3, 3], True),
    (4, [7, 7, 6, 6, 5, 5, 4, 4, 4], True),
    (3, [9, 9, 6, 10, 10], False),
    (2, [1], False),
    (2, [5, 5], False),
    (2, [10, 1, 1, 1, 1, 1, 1, 1, 1, 1], False),
    (3, [1] * 10, False),
    (4, [0, 5, 0], False),
    (5, [17, 17, 14, 10, 10, 7, 7, 6, 6], False),
    (5, [13, 11, 7, 7, 5, 3, 2, 2, 1, 1], False),
    (4, [20, 19, 18, 2, 2, 2, 1, 1, 1, 1], False),
    (3, [6, 5, 4, 3, 3, 3, 2], False),
    (2, [8, 7, 6, 5, 4], False),
    (5, [4, 4, 4, 4, 4, 3, 3, 3, 3, 3], False),
    (4, [14, 13, 12, 10, 10, 8, 7, 6, 6, 1], False),
]


@pytest.mark.parametrize("r,weights,tight", GRAHAM_CASES)
def test_greedy_lpt_within_graham_of_exact_opt(r, weights, tight):
    """Graham's (4/3 − 1/(3r)) factor against the exact optimum:
    3r·LPT <= (4r − 1)·OPT."""
    _, loads = greedy_lpt(np.asarray(weights, np.int64), r)
    lpt, opt = int(loads.max()), exact_makespan(weights, r)
    assert opt <= lpt
    if tight:
        assert 3 * r * lpt == (4 * r - 1) * opt
    else:
        assert 3 * r * lpt <= (4 * r - 1) * opt


@given(st.integers(1, 500), st.integers(1, 8), st.integers(1, 24),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=40, deadline=None)
def test_block_split_covers_all_pairs(n, m, r, seed):
    rng = np.random.default_rng(seed)
    # Zipf-ish skewed block ids
    blocks = (rng.zipf(1.5, size=n) - 1) % max(n // 4, 1)
    parts = rng.integers(0, m, n)
    bdm = compute_bdm(blocks, parts, int(blocks.max()) + 1, m)
    plan = plan_block_split(bdm, r)
    # enumerate every task's pairs in the blocked layout and check the
    # union is exactly the within-block pair set
    got = set()
    for t in range(plan.task_block.shape[0]):
        a0, al = int(plan.task_a_start[t]), int(plan.task_a_len[t])
        b0, bl = int(plan.task_b_start[t]), int(plan.task_b_len[t])
        if plan.task_triangular[t]:
            for i in range(al):
                for j in range(i + 1, al):
                    pair = (a0 + i, a0 + j)
                    assert pair not in got
                    got.add(pair)
        else:
            for i in range(al):
                for j in range(bl):
                    pair = tuple(sorted((a0 + i, b0 + j)))
                    assert pair not in got
                    got.add(pair)
    assert len(got) == plan.total_pairs
    assert plan.reducer_pairs.sum() == plan.total_pairs


@given(sizes_strategy, st.integers(1, 12))
@settings(max_examples=40, deadline=None)
def test_pair_range_materialization_partitions(sizes, r):
    sizes = np.asarray(sizes, np.int64)
    m = 2
    bdm = np.stack([sizes - sizes // 2, sizes // 2], axis=1)
    plan = plan_pair_range(bdm, r)
    seen = set()
    for k in range(r):
        blk, x, y, ra, rb = pairs_of_range(plan, k)
        for t in zip(blk.tolist(), x.tolist(), y.tolist()):
            assert t not in seen
            seen.add(t)
    assert len(seen) == plan.total_pairs


@given(st.integers(0, 300), st.integers(1, 40))
@settings(max_examples=60, deadline=None)
def test_sn_band_index_bijection(n, w):
    total = sn.band_pair_count(n, w)
    assert total == sum(min(w - 1, n - 1 - i) for i in range(max(n - 1, 0)))
    if total == 0:
        return
    p = np.arange(total, dtype=np.int64)
    i, j = sn.invert_band_index(p, n, w)
    assert (0 <= i).all() and (i < j).all() and (j < n).all()
    assert (j - i < max(w, 2)).all()
    np.testing.assert_array_equal(sn.band_pair_index(i, j, n, w), p)


@given(st.integers(0, 250), st.integers(1, 30), st.integers(1, 24))
@settings(max_examples=60, deadline=None)
def test_sn_ranges_partition_band(n, w, r):
    """Every band pair lands in exactly one reduce task; loads conserve."""
    plan = sn.plan_sorted_neighborhood(n, w, r)
    seen = set()
    for k in range(r):
        ra, rb = sn.pairs_of_band_range(plan, k)
        assert ra.shape == rb.shape == (int(plan.reducer_pairs[k]),)
        for t in zip(ra.tolist(), rb.tolist()):
            assert t not in seen
            seen.add(t)
    want = {(i, j) for i in range(n) for j in range(i + 1, min(i + w, n))}
    assert seen == want
    assert int(plan.reducer_pairs.sum()) == plan.total_pairs == len(want)


@given(st.integers(0, 200), st.integers(1, 30), st.integers(1, 16))
@settings(max_examples=60, deadline=None)
def test_sn_map_output_size_closed_form(n, w, r):
    """O(r) gather-interval math == brute-force per-pair gather count."""
    plan = sn.plan_sorted_neighborhood(n, w, r)
    brute = 0
    for k in range(r):
        ra, rb = sn.pairs_of_band_range(plan, k)
        brute += len(set(ra.tolist()) | set(rb.tolist()))
        ivs = sn.band_range_intervals(plan, k)
        assert len(ivs) <= 2                     # the ≤2-interval bound
    assert sn.map_output_size(plan) == brute


@given(st.lists(st.tuples(st.integers(0, 12), st.integers(0, 3)),
                min_size=0, max_size=60),
       st.lists(st.integers(0, 59), min_size=0, max_size=6),
       st.integers(0, 5))
@settings(max_examples=60, deadline=None)
def test_update_bdm_is_compute_bdm_of_concat(stream, cut_points, extra_blocks):
    """Incremental Job 1: folding a (block, partition) stream into the BDM
    batch by batch — ANY split, empty batches included, never-seen blocks
    growing the matrix — equals the one-shot compute_bdm of the
    concatenation. This is the monoid property the resident service's
    ``match()`` path leans on."""
    m = 4
    blocks = np.asarray([b for b, _ in stream], np.int64)
    parts = np.asarray([p for _, p in stream], np.int64)
    nb = int(blocks.max()) + 1 if blocks.size else 0
    nb_forced = nb + extra_blocks            # trailing never-seen blocks
    want = compute_bdm(blocks, parts, nb_forced, m)

    cuts = sorted({min(c, len(stream)) for c in cut_points})
    edges = [0] + cuts + [len(stream)]
    bdm = np.zeros((0, m), np.int64)         # empty seed: identity element
    for lo, hi in zip(edges[:-1], edges[1:]):  # empty slices allowed
        bdm = update_bdm(bdm, blocks[lo:hi], parts[lo:hi])
    bdm = update_bdm(bdm, np.zeros(0, np.int64), np.zeros(0, np.int64),
                     num_blocks=nb_forced)   # growth without entities
    np.testing.assert_array_equal(bdm, want)
    # a second empty fold is a no-op, and the input is never mutated
    again = update_bdm(bdm, np.zeros(0, np.int64), np.zeros(0, np.int64))
    np.testing.assert_array_equal(again, want)


@given(sizes_strategy, st.integers(1, 6))
@settings(max_examples=25, deadline=None)
def test_jnp_inverse_matches_numpy(sizes, r):
    import jax.numpy as jnp

    sizes = np.asarray(sizes, np.int64)
    bdm = sizes[:, None]
    plan = plan_pair_range(bdm, r)
    if plan.total_pairs == 0:
        return
    n_dev = r
    cap = -(-plan.total_pairs // n_dev)
    for dev in range(n_dev):
        ra, rb, valid = pairs_of_range_jnp(
            jnp.asarray(plan.block_sizes, jnp.int32),
            jnp.asarray(plan.offsets, jnp.int32),
            jnp.asarray(plan.estart, jnp.int32),
            jnp.asarray(dev * cap, jnp.int32), cap, plan.total_pairs)
        lo = dev * cap
        hi = min(lo + cap, plan.total_pairs)
        if hi <= lo:
            assert not bool(np.asarray(valid).any())
            continue
        blk, x, y = en.invert_pair_index(
            np.arange(lo, hi), plan.block_sizes, plan.offsets)
        np.testing.assert_array_equal(
            np.asarray(ra)[: hi - lo], plan.estart[blk] + x)
        np.testing.assert_array_equal(
            np.asarray(rb)[: hi - lo], plan.estart[blk] + y)
        assert bool(np.asarray(valid)[: hi - lo].all())
