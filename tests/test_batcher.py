"""The async super-batching serving front-end (DESIGN.md §Serving
pipeline) and the on-device survivor-compaction epilogue it rides on.

Contracts under test:

  * flush policy: a full super-batch flushes immediately
    (``flush_full``), an under-full one flushes when the OLDEST member's
    ``max_delay_s`` budget is spent (``flush_deadline``);
  * batched ≡ sequential: any interleaving of concurrent submissions
    (deterministic seeded sweep + hypothesis leg) demultiplexes to
    EXACTLY the per-request sequential ``ERService.match`` sets —
    including requests larger than the super-batch cap;
  * per-tenant token-bucket admission isolates a hot tenant from the
    shared pipeline and advertises an honest ``retry_after_s``;
  * super-batched serving stays at ZERO steady-state XLA recompiles;
  * compaction parity: the packed prefix-sum epilogue (pallas-interpret
    kernel and its XLA twin) reproduces the dense-mask survivors slot
    for slot — counts exact even past capacity, overflow falls back to
    an exact mask decode — and the compact catalog executor equals the
    reference executor end to end.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import compute_bdm, plan_pair_range
from repro.er import (AdmissionError, ERBatcher, ERConfig, ERService,
                      MatchResponse, ServiceConfig, compile_counter,
                      make_products, run_er)
from repro.er.compiler import (lower, plan_to_job, score_catalog,
                               stage1_stats)
from repro.kernels import ops

DS = make_products(250, seed=3)
CORPUS = DS.titles[:140]
QUERIES = DS.titles[140:170]


def _cfg(**kw):
    base = dict(feature_dim=128, max_len=48, r=8, m=4,
                query_buckets=(8, 32), tile_chunk=64)
    base.update(kw)
    return ServiceConfig(**base)


# One quiet oracle service, memoized per micro-batch: the streaming ≡
# batch contract makes every micro-batch's match set a pure function of
# its titles, so answers are reusable across tests and interleavings.
_ORACLE = {}


def _answer(titles):
    key = tuple(titles)
    if key not in _ORACLE:
        if "svc" not in _ORACLE:
            _ORACLE["svc"] = ERService(CORPUS, _cfg())
        _ORACLE[key] = set(_ORACLE["svc"].match(list(titles)))
    return _ORACLE[key]


# ---------------------------------------------------------------------------
# Super-batching: demux exactness and flush policy
# ---------------------------------------------------------------------------

def test_super_batched_results_equal_sequential():
    svc = ERService(CORPUS, _cfg())
    batches = [QUERIES[:5], QUERIES[5:9], QUERIES[9:16], QUERIES[16:24],
               QUERIES[24:30], QUERIES[:3]]
    with ERBatcher(svc, max_delay_s=0.2) as b:
        futs = [b.submit(q) for q in batches]
        for fut, q in zip(futs, batches):
            resp = fut.result(timeout=120)
            assert isinstance(resp, MatchResponse)
            assert set(resp) == _answer(q)
    assert b.stats["requests"] == len(batches)
    assert b.stats["queries"] == sum(len(q) for q in batches)
    # concurrent submissions coalesced into fewer super-batches
    assert 1 <= b.stats["super_batches"] < len(batches)


def test_flush_on_full_does_not_wait_for_the_deadline():
    svc = ERService(CORPUS, _cfg())
    # delay budget is enormous: only the size trigger can flush
    with ERBatcher(svc, max_delay_s=60.0, max_batch=16) as b:
        futs = [b.submit(QUERIES[i * 4:(i + 1) * 4]) for i in range(4)]
        for i, fut in enumerate(futs):
            got = fut.result(timeout=120)     # resolves in << 60 s
            assert set(got) == _answer(QUERIES[i * 4:(i + 1) * 4])
        assert b.stats["flush_full"] == 1
        assert b.stats["flush_deadline"] == 0
        assert b.stats["super_batches"] == 1
        assert b.stats["max_fill"] == 16


def test_flush_on_deadline_bounds_an_underfull_batch():
    svc = ERService(CORPUS, _cfg())
    with ERBatcher(svc, max_delay_s=0.05, max_batch=32) as b:
        t0 = time.monotonic()
        fut = b.submit(QUERIES[:5])           # never fills the batch
        assert set(fut.result(timeout=120)) == _answer(QUERIES[:5])
        waited = time.monotonic() - t0
        assert b.stats["flush_deadline"] == 1
        assert b.stats["flush_full"] == 0
        assert waited >= 0.03                 # it did hold for the window


def test_oversized_request_is_sliced_and_demuxed():
    svc = ERService(CORPUS, _cfg(query_buckets=(8, 16)))
    big = DS.titles[140:230]                  # 90 queries >> top bucket 16
    with ERBatcher(svc, max_delay_s=0.005) as b:
        fut = b.submit(big)
        small = b.submit(QUERIES[:4])
        assert set(fut.result(timeout=240)) == _answer(big)
        assert set(small.result(timeout=240)) == _answer(QUERIES[:4])


def test_closed_batcher_rejects_new_and_empty_resolves_immediately():
    svc = ERService(CORPUS[:30], _cfg())
    b = ERBatcher(svc, max_delay_s=0.005)
    empty = b.submit([])
    assert empty.result(timeout=5) == set()
    assert b.flush(timeout=10)
    b.close()
    with pytest.raises(RuntimeError):
        b.submit(QUERIES[:2])
    b.close()                                 # idempotent


# ---------------------------------------------------------------------------
# Interleavings: batched ≡ sequential, deterministic sweep + hypothesis
# ---------------------------------------------------------------------------

def _submit_interleaved(batcher, batches, staggers):
    results = [None] * len(batches)

    def worker(i):
        time.sleep(float(staggers[i]))
        results[i] = batcher.submit(batches[i]).result(timeout=240)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(batches))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for got, q in zip(results, batches):
        assert set(got) == _answer(q)


def _partition(cuts):
    bounds = [0] + sorted(cuts) + [len(QUERIES)]
    return [QUERIES[lo:hi] for lo, hi in zip(bounds, bounds[1:]) if hi > lo]


def test_interleaved_submissions_match_sequential_sweep():
    svc = ERService(CORPUS, _cfg())
    rng = np.random.default_rng(11)
    with ERBatcher(svc, max_delay_s=0.01) as b:
        for _ in range(4):
            k = int(rng.integers(1, 6))
            cuts = rng.choice(np.arange(1, len(QUERIES)), size=k,
                              replace=False).tolist()
            batches = _partition(cuts)
            _submit_interleaved(b, batches,
                                rng.uniform(0.0, 0.01, len(batches)))


try:                                          # optional dep — the fuzz leg
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    _HYP = {}

    def _hyp_batcher() -> ERBatcher:
        # one service + batcher across examples: corpus-side state never
        # changes, so per-batch answers stay pure functions of titles
        if "b" not in _HYP:
            _HYP["b"] = ERBatcher(ERService(CORPUS, _cfg()),
                                  max_delay_s=0.005)
        return _HYP["b"]

    @settings(max_examples=12, deadline=None)
    @given(cuts=st.sets(st.integers(1, len(QUERIES) - 1), max_size=6),
           data=st.data())
    def test_any_interleaving_matches_sequential(cuts, data):
        batches = _partition(list(cuts))
        staggers = [data.draw(st.floats(0.0, 0.01)) for _ in batches]
        _submit_interleaved(_hyp_batcher(), batches, staggers)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_any_interleaving_matches_sequential():
        pass


# ---------------------------------------------------------------------------
# Admission control and the recompile guard
# ---------------------------------------------------------------------------

def test_tenant_admission_isolates_hot_tenant():
    svc = ERService(CORPUS, _cfg())
    with ERBatcher(svc, max_delay_s=0.01, tenant_rate=40.0,
                   tenant_burst=8.0) as b:
        hot = [b.submit(QUERIES[:4], tenant="hot"),
               b.submit(QUERIES[4:8], tenant="hot")]     # burst spent
        with pytest.raises(AdmissionError) as ei:
            b.submit(QUERIES[8:12], tenant="hot")
        assert ei.value.tenant == "hot"
        assert ei.value.retry_after_s > 0.0
        # a quiet tenant rides the shared pipeline untouched
        cool = b.submit(QUERIES[8:12], tenant="cool")
        assert set(cool.result(timeout=120)) == _answer(QUERIES[8:12])
        for fut, q in zip(hot, [QUERIES[:4], QUERIES[4:8]]):
            assert set(fut.result(timeout=120)) == _answer(q)
        assert b.stats["rejected"] == 1
        # the advertised wait is honest: the bucket has refilled by then
        time.sleep(ei.value.retry_after_s + 0.05)
        ok = b.submit(QUERIES[8:12], tenant="hot")
        assert set(ok.result(timeout=120)) == _answer(QUERIES[8:12])


def test_super_batched_serving_stays_zero_recompile():
    svc = ERService(CORPUS, _cfg())
    svc.warmup()
    with compile_counter() as cc:
        with ERBatcher(svc, max_delay_s=0.005) as b:
            futs = [b.submit(QUERIES[(i % 3) * 7:(i % 3) * 7 + 7])
                    for i in range(9)]
            for fut in futs:
                fut.result(timeout=240)
    assert cc.count == 0


# ---------------------------------------------------------------------------
# On-device survivor compaction: kernel / twin / executor parity
# ---------------------------------------------------------------------------

BM = BN = 16


def _feats(n: int, seed: int, dim: int = 32) -> np.ndarray:
    rng = np.random.default_rng(seed)
    f = rng.normal(size=(n, dim)).astype(np.float32)
    return f / np.linalg.norm(f, axis=1, keepdims=True)


def _small_catalog(sizes, r=4):
    sizes = np.asarray(sizes, np.int64)
    n = int(sizes.sum())
    bdm = compute_bdm(np.repeat(np.arange(sizes.size), sizes),
                      np.zeros(n, np.int64), sizes.size, 1)
    return lower(plan_to_job(plan_pair_range(bdm, r)), BM, BN), n


def _pairs(ra, rb):
    return set(zip(ra.tolist(), rb.tolist()))


@pytest.mark.parametrize("capacity", (4, 32, BM * BN))
def test_compact_epilogue_parity_and_exact_counts(capacity):
    cat, n = _small_catalog([40, 21, 9], r=4)
    f = _feats(n, 0)
    mask = np.asarray(ops.pair_scores_catalog(
        f, f, cat.tiles, threshold=0.0, block_m=BM, block_n=BN,
        impl="xla")).astype(bool)
    flat = mask.reshape(mask.shape[0], -1)
    counts_want = flat.sum(axis=1)
    assert counts_want.max() > 4              # small caps DO overflow here
    outs = {}
    for impl in ("interpret", "xla"):
        packed, counts = ops.pair_scores_catalog_compact(
            f, f, cat.tiles, threshold=0.0, block_m=BM, block_n=BN,
            capacity=capacity, impl=impl)
        packed = np.asarray(packed)
        counts = np.asarray(counts).reshape(-1)
        # counts stay EXACT even when survivors exceed the capacity —
        # that is what lets the executor detect overflow host-side
        assert (counts == counts_want).all()
        # packed slots are the first min(count, capacity) survivors in
        # row-major order (the order np.nonzero would scan them in)
        for t in range(flat.shape[0]):
            pos = np.flatnonzero(flat[t])
            k = min(pos.size, capacity)
            assert (packed[t, :k] == pos[:k]).all()
            assert (packed[t, k:] == 0).all()  # dead slots zeroed
        outs[impl] = packed
    assert (outs["interpret"] == outs["xla"]).all()


def test_compact_default_capacity_one_tile_past_overflow():
    """At the resolved default capacity (128×128 tiles) exactly one tile
    — a 50-record block, 1,225 live pairs — overflows: interpret ≡ xla
    on packed slots and exact counts, and the executor's fallback keeps
    the match set equal to the mask path."""
    from repro.kernels.pair_sim import resolve_capacity

    bm = bn = 128
    capacity = resolve_capacity(bm, bn)
    sizes = np.asarray([50, 30, 11], np.int64)
    n = int(sizes.sum())
    bdm = compute_bdm(np.repeat(np.arange(sizes.size), sizes),
                      np.zeros(n, np.int64), sizes.size, 1)
    cat = lower(plan_to_job(plan_pair_range(bdm, 1)), bm, bn)
    f = _feats(n, 3)
    mask = np.asarray(ops.pair_scores_catalog(
        f, f, cat.tiles, threshold=-1.0, block_m=bm, block_n=bn,
        impl="xla")).astype(bool)
    flat = mask.reshape(mask.shape[0], -1)
    counts_want = flat.sum(axis=1)
    assert (counts_want > capacity).sum() == 1
    outs = {}
    for impl in ("interpret", "xla"):
        packed, counts = ops.pair_scores_catalog_compact(
            f, f, cat.tiles, threshold=-1.0, block_m=bm, block_n=bn,
            impl=impl)
        packed = np.asarray(packed)
        assert packed.shape == (cat.tiles.shape[0], capacity)
        assert (np.asarray(counts).reshape(-1) == counts_want).all()
        for t in range(flat.shape[0]):
            pos = np.flatnonzero(flat[t])
            k = min(pos.size, capacity)
            assert (packed[t, :k] == pos[:k]).all()
            assert (packed[t, k:] == 0).all()
        outs[impl] = packed
    assert (outs["interpret"] == outs["xla"]).all()

    kw = dict(threshold=-1.0, impl="xla")
    want = _pairs(*score_catalog(f, cat, compact=False, **kw))
    before = dict(stage1_stats)
    assert _pairs(*score_catalog(f, cat, compact=True, **kw)) == want
    assert stage1_stats["compact_overflows"] == \
        before["compact_overflows"] + 1


def test_score_catalog_compact_path_equals_mask_path():
    cat, n = _small_catalog([50, 30, 11], r=6)
    f = _feats(n, 1)
    kw = dict(threshold=0.3, impl="xla", chunk_tiles=8)
    before = dict(stage1_stats)
    want = _pairs(*score_catalog(f, cat, compact=False, **kw))
    assert stage1_stats["nonzero_decodes"] > before["nonzero_decodes"]
    assert stage1_stats["survivors"] - before["survivors"] == len(want) > 0

    before = dict(stage1_stats)
    got = _pairs(*score_catalog(f, cat, compact=True, **kw))
    assert got == want
    assert stage1_stats["survivors"] - before["survivors"] == len(got)
    # at 16×16 the default capacity is bm·bn, which can never overflow:
    # every chunk took the packed epilogue, the host nonzero scan never ran
    assert stage1_stats["compact_decodes"] > before["compact_decodes"]
    assert stage1_stats["nonzero_decodes"] == before["nonzero_decodes"]
    assert stage1_stats["compact_overflows"] == before["compact_overflows"]


def test_compact_overflow_falls_back_exactly():
    cat, n = _small_catalog([50, 30, 11], r=6)
    f = _feats(n, 1)
    kw = dict(threshold=-1.0, impl="xla", chunk_tiles=8)  # ALL pairs live
    want = _pairs(*score_catalog(f, cat, compact=False, **kw))
    before = dict(stage1_stats)
    got = _pairs(*score_catalog(f, cat, compact=True, compact_capacity=2,
                                **kw))
    assert got == want                        # exactness over speed
    assert stage1_stats["compact_overflows"] > before["compact_overflows"]
    assert stage1_stats["nonzero_decodes"] > before["nonzero_decodes"]


def test_run_er_compact_executor_equals_reference():
    titles = DS.titles[:160]
    base = dict(r=8, m=4, feature_dim=128, max_len=48)
    want = run_er(titles, ERConfig(executor="reference", **base)).matches
    for cap in (None, 64):
        got = run_er(titles, ERConfig(executor="catalog",
                                      compact_capacity=cap, **base))
        assert got.matches == want
