"""Tile-catalog executor parity: the fused kernel path (interpret-mode
Pallas and the XLA twin) must produce the IDENTICAL match set as the
reference per-reducer numpy path on a seeded skewed dataset, for all
three strategies — plus the catalog-coverage and map_output_size
invariants the executor rests on."""
import numpy as np
import pytest

from repro.core import (compute_bdm, plan_basic, plan_block_split,
                        plan_pair_range, pairs_of_range)
from pair_range_oracle import EDGE_CASES
from repro.core.pair_range import entity_range_matrix, map_output_size
from repro.er import ERConfig, make_products, run_er
from repro.er.blocking import exponential_block_ids
from repro.er.executor import (build_catalog, catalog_for_cross,
                               enumerate_catalog_pairs, score_catalog)

STRATEGIES = ("basic", "block_split", "pair_range")


@pytest.fixture(scope="module")
def skewed_ds():
    ds = make_products(1200, seed=11)
    rng = np.random.default_rng(11)
    bid = exponential_block_ids(ds.n, b=30, s=1.0, rng=rng)  # Fig. 9 s=1.0
    return ds, bid


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_catalog_matches_reference_interpret(skewed_ds, strategy):
    ds, bid = skewed_ds
    base = dict(strategy=strategy, r=8, m=4, feature_dim=128, max_len=48)
    ref = run_er(ds.titles, ERConfig(executor="reference", **base),
                 block_ids=bid)
    got = run_er(ds.titles, ERConfig(executor="catalog",
                                     kernel_impl="interpret", **base),
                 block_ids=bid)
    assert got.matches == ref.matches
    assert got.total_pairs == ref.total_pairs
    assert got.map_output_size == ref.map_output_size
    np.testing.assert_array_equal(got.reducer_pairs, ref.reducer_pairs)


def test_catalog_matches_reference_xla(skewed_ds):
    """The production CPU path (batched-matmul XLA twin) agrees too."""
    ds, bid = skewed_ds
    base = dict(strategy="block_split", r=8, m=4, feature_dim=128, max_len=48)
    ref = run_er(ds.titles, ERConfig(executor="reference", **base),
                 block_ids=bid)
    got = run_er(ds.titles, ERConfig(kernel_impl="xla", **base),
                 block_ids=bid)
    assert got.matches == ref.matches


def _bdm_fixture(seed=3, b=12, m=4):
    rng = np.random.default_rng(seed)
    bdm = rng.integers(0, 40, (b, m)).astype(np.int64)
    bdm[rng.random(b) < 0.25] = 0          # empty blocks
    bdm[rng.integers(0, b)] = [1, 0, 0, 0]  # singleton block
    return bdm


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("bm,bn", [(32, 32), (32, 64)])
def test_catalog_covers_plan_exactly(strategy, bm, bn):
    """Every planned pair appears in the catalog exactly once (unordered),
    nothing else does — for unaligned strips, empty and singleton blocks."""
    bdm = _bdm_fixture()
    plan = {"basic": plan_basic, "block_split": plan_block_split,
            "pair_range": plan_pair_range}[strategy](bdm, 5)
    cat = build_catalog(plan, block_m=bm, block_n=bn)
    ea, eb = enumerate_catalog_pairs(cat)
    got = {(min(a, b), max(a, b)) for a, b in zip(ea.tolist(), eb.tolist())}
    assert len(got) == ea.size, "catalog covers some pair twice"

    sizes = bdm.sum(axis=1)
    estart = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    want = set()
    for k, s in enumerate(sizes):
        x, y = np.triu_indices(int(s), k=1)
        want.update(zip((estart[k] + x).tolist(), (estart[k] + y).tolist()))
    assert got == want
    assert cat.total_pairs == len(want)


def test_pair_range_catalog_respects_range_partition():
    """Each catalog entry's pairs stay inside its own range's pair-index
    interval (the reducer column is the range id)."""
    bdm = _bdm_fixture(seed=7)
    plan = plan_pair_range(bdm, 6)
    cat = build_catalog(plan, block_m=32, block_n=32)
    for k in range(plan.r):
        sub = cat.tiles[cat.tiles[:, -1] == k]
        if not sub.shape[0]:
            continue
        from repro.er.executor import TileCatalog
        ea, eb = enumerate_catalog_pairs(TileCatalog(
            tiles=sub, block_m=32, block_n=32, n_rows_a=cat.n_rows_a,
            n_rows_b=cat.n_rows_b, r=plan.r, total_pairs=0))
        _, _, _, ra, rb = pairs_of_range(plan, k)
        want = set(zip(ra.tolist(), rb.tolist()))
        assert set(zip(ea.tolist(), eb.tolist())) == want


def test_cross_catalog_two_source():
    """Rectangular A×B catalog scores against two distinct matrices."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal((70, 32)).astype(np.float32)
    b = rng.standard_normal((23, 32)).astype(np.float32)
    a /= np.linalg.norm(a, axis=1, keepdims=True)
    b /= np.linalg.norm(b, axis=1, keepdims=True)
    cat = catalog_for_cross(70, 23, r=3, block_m=32, block_n=32)
    ca, cb = score_catalog(a, cat, b, threshold=0.2, impl="interpret",
                           chunk_tiles=4)
    cos = a @ b.T
    wa, wb = np.nonzero(cos >= 0.2)
    assert set(zip(ca.tolist(), cb.tolist())) == set(zip(wa.tolist(),
                                                         wb.tolist()))


def _random_bdms():
    rng = np.random.default_rng(5)
    for _ in range(25):
        bdm = rng.integers(0, 25, (rng.integers(1, 10), rng.integers(1, 4)))
        yield bdm, int(rng.integers(1, 7))


@pytest.mark.parametrize("case", ["random"] + sorted(EDGE_CASES))
def test_map_output_size_closed_form_equals_bruteforce(case):
    """The O(r + b) map_output_size equals the brute-force per-pair oracle
    (and run_er no longer emits the -1 sentinel), on 25 seeded BDMs and
    on each PairRange edge case."""
    cases = _random_bdms() if case == "random" else [EDGE_CASES[case]]
    for bdm, r in cases:
        plan = plan_pair_range(bdm, r)
        assert map_output_size(plan) == int(entity_range_matrix(plan).sum())
