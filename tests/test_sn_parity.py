"""Sorted Neighborhood parity: the SN strategy through the tile-catalog
executor (interpret-mode Pallas and the XLA twin) and the reference
per-reducer numpy path must all produce the IDENTICAL match set as the
O(n·w) windowed-pair brute-force oracle on a seeded skewed dataset —
mirroring test_executor_parity.py — plus the band-catalog coverage
invariants and the acceptance balance bar (max/mean ≤ 1.2 at r=32)."""
import numpy as np
import pytest
from sn_oracle import sn_band_pairs_bruteforce, sn_oracle_matches

from repro.core import plan_sorted_neighborhood
from repro.core.sorted_neighborhood import pairs_of_band_range
from repro.er import ERConfig, make_products, run_er
from repro.er.blocking import exponential_block_ids
from repro.er.executor import build_catalog, enumerate_catalog_pairs

WINDOW = 12
BASE = dict(strategy="sorted_neighborhood", window=WINDOW, r=8,
            feature_dim=128, max_len=48)


@pytest.fixture(scope="module")
def skewed_ds():
    # Same seeded skewed corpus as test_executor_parity (the Fig. 9
    # skew=1.0 block ids exist for the balance test; SN itself slides a
    # window over the sort order, independent of any block distribution).
    ds = make_products(1200, seed=11)
    rng = np.random.default_rng(11)
    bid = exponential_block_ids(ds.n, b=30, s=1.0, rng=rng)
    return ds, bid


@pytest.fixture(scope="module")
def oracle(skewed_ds):
    ds, _ = skewed_ds
    return sn_oracle_matches(ds.titles, WINDOW, feature_dim=128, max_len=48)


@pytest.mark.parametrize("kernel_impl", ["interpret", "xla"])
def test_sn_catalog_matches_oracle(skewed_ds, oracle, kernel_impl):
    """Acceptance bar: exact oracle match set for both kernel impls."""
    ds, _ = skewed_ds
    res = run_er(ds.titles, ERConfig(executor="catalog",
                                     kernel_impl=kernel_impl, **BASE))
    assert res.matches == oracle
    assert res.total_pairs == res.reducer_pairs.sum()


def test_sn_reference_matches_oracle(skewed_ds, oracle):
    ds, _ = skewed_ds
    res = run_er(ds.titles, ERConfig(executor="reference", **BASE))
    assert res.matches == oracle


def test_sn_end_to_end_executor_leg(skewed_ds, oracle, executor):
    """The CI matrix leg: whole SN pipeline under --executor=<leg>."""
    ds, _ = skewed_ds
    res = run_er(ds.titles, ERConfig(executor=executor, **BASE))
    assert res.matches == oracle
    assert res.map_output_size > 0


def test_sn_balance_on_fig9_skew(skewed_ds):
    """Acceptance bar: reducer-load imbalance (max/mean planned pairs)
    ≤ 1.2 at r=32 — the band partition is skew-free by construction, so
    the Fig. 9 s=1.0 block distribution cannot unbalance it."""
    ds, bid = skewed_ds
    cfg = ERConfig(strategy="sorted_neighborhood", window=WINDOW, r=32,
                   feature_dim=128, max_len=48)
    res = run_er(ds.titles, cfg, block_ids=bid)   # block_ids ignored by SN
    loads = res.reducer_pairs
    assert loads.sum() == res.total_pairs
    assert loads.max() / loads.mean() <= 1.2


def test_sn_window_covers_full_triangle_at_w_ge_n():
    """w ≥ n degenerates to the all-pairs triangle."""
    n = 40
    plan = plan_sorted_neighborhood(n, n + 5, 4)
    assert plan.total_pairs == n * (n - 1) // 2
    seen = set()
    for k in range(plan.r):
        ra, rb = pairs_of_band_range(plan, k)
        seen.update(zip(ra.tolist(), rb.tolist()))
    assert seen == sn_band_pairs_bruteforce(n, n + 5)


@pytest.mark.parametrize("bm,bn", [(32, 32), (32, 64)])
@pytest.mark.parametrize("n,w,r", [(300, 17, 7), (130, 64, 3), (50, 2, 5)])
def test_sn_catalog_covers_band_exactly(n, w, r, bm, bn):
    """Every band pair appears in the band-diagonal catalog exactly once,
    nothing else does — for unaligned strips and off-diagonal windows."""
    plan = plan_sorted_neighborhood(n, w, r)
    cat = build_catalog(plan, block_m=bm, block_n=bn)
    ea, eb = enumerate_catalog_pairs(cat)
    got = set(zip(ea.tolist(), eb.tolist()))
    assert len(got) == ea.size, "catalog covers some band pair twice"
    assert got == sn_band_pairs_bruteforce(n, w)
    assert cat.total_pairs == len(got)


def test_sn_catalog_tiles_hug_the_band():
    """The tile count scales with the band, not the n×n triangle: a thin
    window over many rows must not emit O((n/bm)^2) tiles."""
    plan = plan_sorted_neighborhood(4096, 10, 8)
    cat = build_catalog(plan, block_m=128, block_n=128)
    n_strips = 4096 // 128
    assert cat.num_tiles <= 3 * n_strips        # ~2 per strip row for w≪bm
    assert cat.num_tiles < n_strips * n_strips / 4


@pytest.mark.parametrize("n,w,n_dev", [(300, 17, 4), (301, 90, 4),
                                       (130, 64, 3), (50, 2, 8)])
def test_sn_halo_job_covers_band_exactly_diagonal_first(n, w, n_dev):
    """The RepSN job (one shard-local banded task per device) covers every
    band pair exactly once when each device's tiles are shifted by its
    shard origin, including row padding and the halo that wraps round to
    the last shard; each device lists its diagonal tiles first."""
    from repro.er.compiler import (A_TILE, B_TILE, RED, TileCatalog, lower,
                                   sn_halo_job)
    n_loc = -(-n // n_dev)
    plan = plan_sorted_neighborhood(n, w, 5)
    cat = lower(sn_halo_job(plan, n_dev, n_loc), 32, 32)
    got = []
    for d in range(n_dev):
        mine = cat.tiles[cat.tiles[:, RED] == d]
        off = mine[:, B_TILE] - mine[:, A_TILE]
        assert (np.diff(off) >= 0).all()
        ea, eb = enumerate_catalog_pairs(TileCatalog(
            mine, 32, 32, cat.n_rows_a, cat.n_rows_b, cat.r,
            cat.total_pairs))
        got += list(zip((ea + d * n_loc).tolist(), (eb + d * n_loc).tolist()))
    assert len(got) == len(set(got)) == plan.total_pairs
    assert set(got) == sn_band_pairs_bruteforce(n, w)
