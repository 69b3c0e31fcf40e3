"""Unified match-job compiler + cost-model scheduler (deterministic leg).

The invariants, shared with the hypothesis leg
(``test_schedule_properties.py`` imports the ``check_*`` functions and
fuzzes their inputs):

  * the exact tile cost model: per-tile live-pair counts equal the
    enumeration oracle and sum to the plan's total, for every strategy's
    geometry (windows, tri, corner cuts, the SN band);
  * scheduling is a pure permutation of ownership: any schedule (either
    policy, any device count) preserves catalog coverage and
    disjointness exactly, both through ``apply_schedule`` and through
    the per-device tile shards;
  * cost-LPT beats the reducer round-robin baseline on skewed BDMs
    (dominant-block Basic instances — the paper's skew-collapse case)
    and never loses more than one tile quantum on any instance;
  * exact match-set parity through the unified plan → job → catalog →
    schedule → execute path vs the reference executor for all five
    strategies (basic / block_split / pair_range / sorted_neighborhood /
    the two-source service), under both schedule policies.
"""
import numpy as np
import pytest

from repro.core import (compute_bdm, plan_basic, plan_block_split,
                        plan_pair_range, plan_sorted_neighborhood)
from repro.core.two_source import TwoSourceBDM, plan_pair_range_2src
from repro.er import (ERConfig, ERService, ServiceConfig, cross_restrict,
                      make_products, run_er)
from repro.er.blocking import exponential_block_ids
from repro.er.compiler import (apply_schedule, cross_job, device_assignment,
                               enumerate_catalog_pairs, lower, plan_to_job,
                               schedule_tiles, tile_costs, tiles_for_devices)
from repro.er.compiler.ir import R1, RED, TileCatalog


# ---------------------------------------------------------------------------
# Shared check functions (fuzzed by test_schedule_properties.py)
# ---------------------------------------------------------------------------

def pair_multiset(catalog):
    ea, eb = enumerate_catalog_pairs(catalog)
    pairs = sorted(zip(ea.tolist(), eb.tolist()))
    assert len(pairs) == len(set(pairs)), "catalog covers some pair twice"
    return pairs


def _sub_catalog(cat, tiles):
    return TileCatalog(tiles=tiles, block_m=cat.block_m, block_n=cat.block_n,
                       n_rows_a=cat.n_rows_a, n_rows_b=cat.n_rows_b,
                       r=cat.r, total_pairs=0)


def check_tile_costs_exact(cat):
    """Closed-form per-tile live counts == the per-tile enumeration oracle,
    summing to the plan's exact pair count."""
    costs = tile_costs(cat)
    assert costs.shape[0] == cat.num_tiles
    per_tile = np.asarray(
        [len(pair_multiset(_sub_catalog(cat, cat.tiles[i:i + 1])))
         for i in range(cat.num_tiles)], np.int64)
    np.testing.assert_array_equal(costs, per_tile)
    assert int(costs.sum()) == cat.total_pairs


def check_schedule_preserves_coverage(cat, n_dev, policy):
    """A schedule moves ownership, never pairs: coverage/disjointness are
    preserved through apply_schedule AND through the device shards."""
    want = pair_multiset(cat)
    sched = schedule_tiles(cat, n_dev=n_dev, policy=policy)
    assert pair_multiset(apply_schedule(cat, sched)) == want
    assert (sched.tile_reducer >= 0).all()
    assert (sched.tile_reducer < cat.r).all()
    assert (0 <= sched.reducer_device).all()
    assert (sched.reducer_device < n_dev).all()
    assert int(sched.reducer_load.sum()) == cat.total_pairs
    assert int(sched.device_load.sum()) == cat.total_pairs

    tiles_dev = tiles_for_devices(cat, n_dev, schedule=sched)
    got = []
    for d in range(n_dev):
        shard = tiles_dev[d]
        live = shard[shard[:, R1] > 0]   # padding rows have empty windows
        got += pair_multiset(_sub_catalog(cat, live))
        # every live tile on device d is owned by a reducer placed on d
        assert (sched.reducer_device[live[:, RED]] == d).all()
    assert sorted(got) == want


def check_lpt_beats_round_robin(bdm, r, n_dev):
    """Basic hash-partitioning pins the dominant block's pairs to one
    reducer → one device; tile-level cost-LPT spreads them."""
    cat = lower(plan_to_job(plan_basic(bdm, r)), 32, 32)
    rr = schedule_tiles(cat, n_dev=n_dev, policy="round_robin")
    lpt = schedule_tiles(cat, n_dev=n_dev, policy="cost_lpt")
    assert int(lpt.device_load.max()) < int(rr.device_load.max())


def check_lpt_within_tile_quantum(cat, n_dev):
    """On ALREADY balanced plans (PairRange's ceil split) tile-level LPT
    cannot beat the exact pair split — but it never loses more than one
    tile of quantization."""
    rr = schedule_tiles(cat, n_dev=n_dev, policy="round_robin")
    lpt = schedule_tiles(cat, n_dev=n_dev, policy="cost_lpt")
    slack = int(lpt.tile_cost.max()) if lpt.tile_cost.size else 0
    assert int(lpt.device_load.max()) <= int(rr.device_load.max()) + slack


# ---------------------------------------------------------------------------
# Deterministic instance generators (the hypothesis leg draws its own)
# ---------------------------------------------------------------------------

def _rng_bdm(rng):
    b, m = int(rng.integers(1, 10)), int(rng.integers(1, 4))
    bdm = rng.integers(0, 12, (b, m)).astype(np.int64)
    if rng.random() < 0.5:
        bdm[int(rng.integers(0, b))] = int(rng.integers(20, 50))
    return bdm


def _catalog_zoo(rng):
    """One lowered catalog per strategy geometry, randomized instance."""
    r = int(rng.integers(1, 6))
    bm = int(rng.choice([16, 32]))
    bdm = _rng_bdm(rng)
    yield lower(plan_to_job(plan_basic(bdm, r)), bm, bm)
    yield lower(plan_to_job(plan_block_split(bdm, r)), bm, bm)
    yield lower(plan_to_job(plan_pair_range(bdm, r)), bm, bm)
    yield lower(plan_to_job(plan_sorted_neighborhood(
        int(rng.integers(2, 200)), int(rng.integers(2, 30)), r)), bm, bm)
    ra, rb_ = _rng_bdm(rng), _rng_bdm(rng)
    b = min(ra.shape[0], rb_.shape[0])
    bdm2 = TwoSourceBDM(bdm_r=ra[:b], bdm_s=rb_[:b])
    yield lower(plan_to_job(plan_pair_range_2src(bdm2, r)), bm, bm)
    yield lower(cross_job(int(rng.integers(1, 80)),
                          int(rng.integers(1, 40)), r), bm, bm)


def test_tile_costs_exact_all_strategies():
    rng = np.random.default_rng(7)
    for _ in range(6):
        for cat in _catalog_zoo(rng):
            check_tile_costs_exact(cat)


def test_schedule_preserves_coverage_all_strategies():
    rng = np.random.default_rng(11)
    for trial in range(4):
        for cat in _catalog_zoo(rng):
            check_schedule_preserves_coverage(
                cat, n_dev=int(rng.integers(1, 9)),
                policy=("cost_lpt", "round_robin")[trial % 2])


def test_cost_lpt_beats_round_robin_on_skew():
    rng = np.random.default_rng(13)
    for _ in range(10):
        b, m = int(rng.integers(3, 12)), int(rng.integers(1, 4))
        bdm = rng.integers(0, 6, (b, m)).astype(np.int64)
        big = int(rng.integers(128, 300))
        bdm[int(rng.integers(0, b))] = [big // m + (i < big % m)
                                        for i in range(m)]
        check_lpt_beats_round_robin(bdm, r=int(rng.integers(4, 16)),
                                    n_dev=int(rng.integers(2, 8)))


def test_cost_lpt_never_worse_than_a_tile_quantum():
    rng = np.random.default_rng(17)
    for _ in range(4):
        for cat in _catalog_zoo(rng):
            check_lpt_within_tile_quantum(cat, n_dev=int(rng.integers(2, 9)))


def test_schedule_respects_healthy_mask():
    rng = np.random.default_rng(19)
    for cat in _catalog_zoo(rng):
        healthy = np.array([False, True, True, False, True])
        sched = schedule_tiles(cat, n_dev=5, healthy=healthy,
                               policy="cost_lpt")
        dead = np.flatnonzero(~healthy)
        assert not np.isin(sched.reducer_device, dead).any()
        assert sched.device_load[dead].sum() == 0


def test_device_assignment_respreads_over_healthy_devices():
    """Without a schedule, reducers go round-robin over the healthy
    devices only, evenly to within one."""
    healthy = np.ones(8, bool)
    healthy[[2, 5]] = False
    assign = device_assignment(32, 8, healthy)
    assert set(assign.tolist()) == set(np.flatnonzero(healthy).tolist())
    counts = np.bincount(assign, minlength=8)
    assert counts[~healthy].sum() == 0
    assert counts[healthy].max() - counts[healthy].min() <= 1


def test_tiles_for_devices_routes_reducers_round_robin():
    """Without a schedule, every live tile of a two-source catalog lands
    on its reducer's round-robin device; padding entries are empty."""
    qb = np.asarray([0, 0, 1, 2] * 4)
    bdm2 = TwoSourceBDM(
        bdm_r=compute_bdm(np.arange(16) % 3, np.zeros(16, np.int64), 3, 1),
        bdm_s=compute_bdm(qb, np.zeros_like(qb), 3, 1))
    cat = lower(plan_to_job(plan_pair_range_2src(bdm2, 16)), 16, 16)
    tiles_dev = tiles_for_devices(cat, 8)
    dev_of = device_assignment(16, 8)
    live = 0
    for d in range(8):
        mine = tiles_dev[d][tiles_dev[d][:, R1] > 0]
        assert (dev_of[mine[:, RED]] == d).all()
        live += mine.shape[0]
    assert live == cat.num_tiles


# ---------------------------------------------------------------------------
# (c) match-set parity through the unified path, all five strategies
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def parity_ds():
    ds = make_products(700, seed=23)
    rng = np.random.default_rng(23)
    bid = exponential_block_ids(ds.n, b=20, s=1.0, rng=rng)
    return ds, bid


@pytest.mark.parametrize("strategy", ["basic", "block_split", "pair_range",
                                      "sorted_neighborhood"])
@pytest.mark.parametrize("policy", ["cost_lpt", "round_robin"])
def test_unified_path_parity(parity_ds, strategy, policy):
    """run_er through plan_to_job → lower → schedule → execute equals the
    reference per-reducer numpy executor — identical match sets under
    either schedule policy (scheduling moves work, never pairs)."""
    ds, bid = parity_ds
    base = dict(strategy=strategy, r=6, m=4, feature_dim=128, max_len=48,
                window=9)
    bids = None if strategy == "sorted_neighborhood" else bid
    ref = run_er(ds.titles, ERConfig(executor="reference", **base),
                 block_ids=bids)
    got = run_er(ds.titles, ERConfig(executor="catalog", kernel_impl="xla",
                                     schedule_policy=policy, **base),
                 block_ids=bids)
    assert got.matches == ref.matches
    assert got.total_pairs == ref.total_pairs
    np.testing.assert_array_equal(got.reducer_pairs, ref.reducer_pairs)
    assert got.schedule is not None and got.schedule["policy"] == policy
    assert got.schedule["total_cost"] == int(ref.reducer_pairs.sum())


@pytest.mark.parametrize("policy", ["cost_lpt", "round_robin"])
def test_unified_path_parity_two_source_service(parity_ds, policy):
    """The fifth strategy: the service's two-source query jobs through the
    same compiler equal the batch cross_restrict oracle."""
    ds, _ = parity_ds
    corpus = ds.titles[:240] + [""]
    queries = ds.titles[240:290] + ["", "@@@ fresh block"]
    cfg = ServiceConfig(feature_dim=128, max_len=48, r=8, m=4,
                        query_buckets=(16, 64), tile_chunk=32,
                        schedule_policy=policy)
    svc = ERService(corpus, cfg)
    got, off = set(), 0
    for sz in (17, 16, len(queries) - 33):
        for a, b in svc.match(queries[off:off + sz]):
            got.add((a, b + off))
        off += sz
    oracle = run_er(corpus + queries,
                    ERConfig(feature_dim=128, max_len=48, r=8, m=4))
    assert got == cross_restrict(oracle.matches, len(corpus))
