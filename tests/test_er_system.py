"""End-to-end ER system behaviour (paper workflow on one host):
all three strategies find identical matches, recall on injected
duplicates is ~1.0, balance metrics ordered Basic ≫ BlockSplit ≥
PairRange, Fig. 12 map-output ordering, two-source + missing-key paths.
"""
import numpy as np
import pytest

from repro.core import compute_bdm, plan_basic, plan_block_split, plan_pair_range
from repro.core.two_source import (TwoSourceBDM, plan_block_split_2src,
                                   plan_pair_range_2src, pairs_of_range_2src)
from repro.er import ERConfig, make_products, run_er
from repro.er.blocking import exponential_block_ids, prefix_block_ids
from repro.er.similarity import edit_distance, edit_distance_np
from repro.er.encode import encode_titles, ngram_features


@pytest.fixture(scope="module")
def ds():
    # large enough that the generator's head-block pair-share calibration
    # holds (integer rounding washes it out below ~10k entities)
    return make_products(12_000, seed=0)


@pytest.fixture(scope="module")
def results(ds):
    return {
        strat: run_er(ds.titles, ERConfig(strategy=strat, r=16, m=8))
        for strat in ("basic", "block_split", "pair_range")
    }


def test_strategies_agree_and_recall(ds, results):
    match_sets = [r.matches for r in results.values()]
    assert match_sets[0] == match_sets[1] == match_sets[2]
    recall = len(match_sets[0] & ds.true_pairs) / len(ds.true_pairs)
    assert recall >= 0.98
    # precision is not 1.0 (near-duplicate generated titles) but bounded
    assert len(match_sets[0]) < 50 * len(ds.true_pairs)


def test_balance_ordering(results):
    mx = {k: int(v.reducer_pairs.max()) for k, v in results.items()}
    total = results["basic"].total_pairs
    # Basic pinned to the largest block (~70% of pairs); balanced ≈ P/r
    assert mx["basic"] > 0.4 * total
    assert mx["basic"] > 5 * mx["pair_range"]
    assert mx["pair_range"] == -(-total // 16)
    assert mx["block_split"] <= 2 * mx["pair_range"]


def test_map_output_ordering(results):
    # Fig. 12: basic = n (no replication) < block_split <= pair_range-ish
    basic = results["basic"].map_output_size
    bs = results["block_split"].map_output_size
    assert basic < bs


def test_skewed_blocking_override(ds):
    rng = np.random.default_rng(0)
    bid = exponential_block_ids(ds.n, b=50, s=1.0, rng=rng)
    res = run_er(ds.titles, ERConfig(strategy="pair_range", r=8, m=4),
                 block_ids=bid)
    assert res.total_pairs > 0
    assert res.reducer_pairs.max() == -(-res.total_pairs // 8)


def test_missing_keys_matched():
    titles = ["", " ", "abc laptop pro 0001", "abc laptop pro 0001"]
    res = run_er(titles, ERConfig(strategy="pair_range", r=2, m=1))
    assert (2, 3) in res.matches
    assert res.extra.get("null_key_pairs", 0) > 0


def test_two_source_plans_cover():
    rng = np.random.default_rng(3)
    bdm2 = TwoSourceBDM(bdm_r=rng.integers(0, 5, (6, 2)),
                        bdm_s=rng.integers(0, 5, (6, 3)))
    total = int((bdm2.sizes_r * bdm2.sizes_s).sum())
    p2 = plan_pair_range_2src(bdm2, 4)
    assert p2.total_pairs == total
    seen = set()
    for k in range(4):
        blk, x, y, rr, rs = pairs_of_range_2src(p2, k)
        for t in zip(blk.tolist(), x.tolist(), y.tolist()):
            assert t not in seen
            seen.add(t)
    assert len(seen) == total
    b2 = plan_block_split_2src(bdm2, 4)
    assert b2.total_pairs == total
    assert b2.reducer_pairs.sum() == total


def test_edit_distance_matches_reference():
    rng = np.random.default_rng(0)
    words = ["kitten", "sitting", "acme laptop pro", "acme laptop pr",
             "zzz", "", "a", "load balancing for mapreduce"]
    pairs = [(a, b) for a in words for b in words]
    ca, la = encode_titles([p[0] for p in pairs], 32)
    cb, lb = encode_titles([p[1] for p in pairs], 32)
    got = np.asarray(edit_distance(ca, la, cb, lb))
    want = [edit_distance_np(a, b) for a, b in pairs]
    np.testing.assert_array_equal(got, want)


# Pairs at similarity exactly 0.8 (d / max length = 1/5) and one edit
# beyond it.
THRESHOLD_PAIRS = [("aac max pro 961", "aac max pro 589"),   # 3 edits, 15 bytes
                   ("aac max pro 961", "aac max pr 5890"),   # 4 edits, 15 bytes
                   ("abcde", "abcdf"), ("abcde", "abdff"),
                   ("x" * 20, "x" * 16 + "yyyy"), ("x" * 25, "x" * 20 + "yyyyy"),
                   ("x" * 25, "x" * 19 + "yyyyyy")]


def test_verify_pairs_keeps_pairs_exactly_at_the_threshold():
    """Similarity exactly 0.8 (d / max length = 1/5) passes stage 2 and
    one edit more fails, as in the exact rule 10·d <= 2·max length."""
    from repro.er.compiler import verify_pairs

    pairs = THRESHOLD_PAIRS
    titles = [t for p in pairs for t in p]
    codes, lens = encode_titles(titles, 64)
    rows_a, rows_b = np.arange(0, len(titles), 2), np.arange(1, len(titles), 2)
    hit_a, _ = verify_pairs(codes, lens, codes, lens, rows_a, rows_b,
                            threshold=0.8)
    dist = np.array([edit_distance_np(a, b) for a, b in pairs])
    mx = np.array([max(len(a), len(b)) for a, b in pairs])
    np.testing.assert_array_equal(hit_a, rows_a[10 * dist <= 2 * mx])
    assert (10 * dist == 2 * mx).sum() == 4


def test_edit_similarity_is_the_ieee_quotient_inside_jit():
    """``edit_similarity`` equals 1 − d / max length divided in IEEE
    float32, bit for bit, and a jitted ``two_stage_match`` keeps the
    pairs exactly at 0.8 and drops those one edit beyond."""
    import jax
    from repro.er.similarity import edit_similarity, two_stage_match

    a, b = (list(t) for t in zip(*THRESHOLD_PAIRS))
    ca, la = encode_titles(a, 64)
    cb, lb = encode_titles(b, 64)
    dist = np.array([edit_distance_np(x, y) for x, y in THRESHOLD_PAIRS])
    mx = np.maximum(la, lb)
    want = 1.0 - dist.astype(np.float32) / mx.astype(np.float32)
    sim = np.asarray(edit_similarity(ca, la, cb, lb))
    np.testing.assert_array_equal(sim.view(np.uint32), want.view(np.uint32))

    # A margin of 1 lets every pair past the cosine filter.
    match, scores = jax.jit(two_stage_match)(
        ngram_features(a), ngram_features(b), ca, la, cb, lb, 0.8, 1.0)
    np.testing.assert_array_equal(np.asarray(match), 10 * dist <= 2 * mx)
    np.testing.assert_array_equal(np.asarray(scores).view(np.uint32),
                                  np.where(match, want, 0).view(np.uint32))


def test_default_config_fresh_per_call():
    """run_er must not share a mutable default ERConfig across calls:
    the default is None → a fresh instance, returned on ERResult.config,
    so mutating a returned config cannot leak into later calls."""
    import inspect

    from repro.er.pipeline import run_er as _run_er

    assert inspect.signature(_run_er).parameters["config"].default is None
    titles = ["abc laptop pro 0001", "abc laptop pro 0002",
              "abd phone max 0003", "abd phone max 0004"]
    res1 = run_er(titles)
    assert res1.config is not None and res1.config.threshold == 0.8
    res1.config.threshold = 0.0          # sabotage the returned config
    res1.config.strategy = "basic"
    res2 = run_er(titles)                # fresh default, unaffected
    assert res2.config is not res1.config
    assert res2.config.threshold == 0.8
    assert res2.config.strategy == "pair_range"
    assert res2.matches == res1.matches


def test_ngram_features_unit_norm_and_determinism():
    titles = ["acme laptop", "acme laptop", "zzz", "ab"]
    f1 = ngram_features(titles, dim=64)
    f2 = ngram_features(titles, dim=64)
    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_allclose(np.linalg.norm(f1, axis=1), 1.0, rtol=1e-5)
    np.testing.assert_array_equal(f1[0], f1[1])
    assert not np.array_equal(f1[0], f1[2])
