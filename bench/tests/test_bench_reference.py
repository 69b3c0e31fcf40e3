"""The benchmark's plain reference against the program, on the CPU.

The reference (``bench/reference.py``) imports nothing of the program;
here it must give the program's answers: ``run_er`` with the reference
executor and with the catalog path on DS1- and DS2-shaped corpora, and
``ERService`` behind ``ERBatcher`` on a served stream.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

import benchkit  # noqa: F401  (puts bench/ and src/ on the path)
from benchkit import restore_jax_config  # noqa: F401
from corpus import build_corpus, perturb, seed_rng
import reference

DS1 = dict(n_records=2500, head_frac=0.018, pair_share=0.71, dup_frac=0.05)
DS2 = dict(n_records=2500, head_frac=0.04, pair_share=0.26, dup_frac=0.03)


def levenshtein(a: bytes, b: bytes) -> int:
    prev = list(range(len(b) + 1))
    for i in range(1, len(a) + 1):
        cur = [i] + [0] * len(b)
        for j in range(1, len(b) + 1):
            cur[j] = min(prev[j] + 1, cur[j - 1] + 1,
                         prev[j - 1] + (a[i - 1] != b[j - 1]))
        prev = cur
    return prev[-1]


def test_edit_distance_is_levenshtein():
    rng = np.random.default_rng(0)
    words = ["", "a", "ab", "abc laptop phone 0001", "x" * 64,
             "abd laptop phone 0010", "zzz mouse hub 9999"]
    words += ["".join(rng.choice(list("abcde "), int(rng.integers(0, 70))))
              for _ in range(60)]
    pairs = [(a, b) for a in words[:20] for b in words]
    sem = reference.Semantics()
    codes_a, lens_a, _, _ = reference.featurize([a for a, _ in pairs], sem)
    codes_b, lens_b, _, _ = reference.featurize([b for _, b in pairs], sem)
    got = reference.edit_distance(codes_a, lens_a, codes_b, lens_b)
    want = [levenshtein(a.encode()[:64], b.encode()[:64]) for a, b in pairs]
    assert got.tolist() == want


def test_edit_threshold_matches_float32_program():
    """The reference decides 1 - d/max_len >= 0.8 exactly; the program
    in float32. They agree for every distance and length it can see."""
    from repro.er.similarity import edit_similarity
    import jax.numpy as jnp
    m = np.repeat(np.arange(1, 65), 65)
    d = np.tile(np.arange(65), 64)
    keep = d <= m
    m, d = m[keep], d[keep]
    exact = reference.edit_matches(d, m, m, 0.8)
    # strings of length m at distance d: d substitutions
    a = np.zeros((m.size, 64), np.uint8)
    b = np.zeros((m.size, 64), np.uint8)
    for i, (mi, di) in enumerate(zip(m, d)):
        a[i, :mi] = ord("a")
        b[i, :mi] = ord("a")
        b[i, :di] = ord("b")
    sim = np.asarray(edit_similarity(jnp.asarray(a), jnp.asarray(m),
                                     jnp.asarray(b), jnp.asarray(m)))
    assert np.array_equal(sim >= 0.8, exact)


def test_features_match_program():
    from repro.er.encode import ngram_features
    titles = build_corpus(DS1, 3).titles[:500] + ["", "ab", "x" * 80]
    _, _, counts, sq = reference.featurize(titles, reference.Semantics())
    norm = counts / np.sqrt(np.maximum(sq, 1))[:, None]
    np.testing.assert_allclose(norm, ngram_features(titles), atol=1e-6)


@pytest.mark.parametrize("params", [DS1, DS2], ids=["ds1", "ds2"])
@pytest.mark.parametrize("executor", ["reference", "catalog"])
def test_dedup_reference_equals_run_er(params, executor):
    from repro.er import ERConfig, run_er
    corpus = build_corpus(params, 11)
    sem = reference.Semantics(prefix_len=corpus.prefix_len)
    ref = reference.dedup_reference(corpus.titles, sem)
    got = run_er(corpus.titles, ERConfig(
        r=100, m=20, prefix_len=corpus.prefix_len, executor=executor)).matches
    assert len(ref.sure) > 50
    assert reference.compare(got, ref) == (0, 0)


def test_dedup_reference_splits_no_block():
    """Every same-key pair is examined once, the head block included."""
    corpus = build_corpus(DS1, 4)
    sem = reference.Semantics(prefix_len=corpus.prefix_len)
    ref = reference.dedup_reference(corpus.titles, sem)
    keys = {}
    for t in corpus.titles:
        keys[t[:corpus.prefix_len]] = keys.get(t[:corpus.prefix_len], 0) + 1
    sizes = np.array(list(keys.values()))
    assert ref.pairs_examined == int((sizes * (sizes - 1) // 2).sum())


def test_cross_reference_equals_service_stream():
    """A served stream through ERBatcher, its cross-restricted batch
    oracle, and the reference agree."""
    from repro.er import (ERBatcher, ERConfig, ERService, ServiceConfig,
                          cross_restrict, run_er)
    corpus = build_corpus(DS1, 8)
    rng = seed_rng(8, 9)
    picks = rng.integers(0, corpus.n, 200)
    queries = [perturb(rng, corpus.titles[int(i)], corpus.prefix_len)
               for i in picks]
    sem = reference.Semantics(prefix_len=corpus.prefix_len)
    ref = reference.cross_reference(corpus.titles, queries, sem)
    batch = cross_restrict(run_er(corpus.titles + queries, ERConfig(
        r=100, m=20, prefix_len=corpus.prefix_len,
        executor="reference")).matches, corpus.n)
    svc = ERService(corpus.titles, ServiceConfig(
        r=100, m=20, prefix_len=corpus.prefix_len))
    got = set()
    with ERBatcher(svc) as batcher:
        sizes = rng.integers(1, 17, 40)
        offs = np.concatenate([[0], np.cumsum(sizes)])
        offs = offs[offs <= len(queries)]
        futs = [(lo, batcher.submit(queries[lo:hi]))
                for lo, hi in zip(offs[:-1], offs[1:])]
        for lo, f in futs:
            got |= {(a, lo + b) for a, b in f.result(timeout=120)}
        n_q = int(offs[-1])
    sub = {p for p in batch if p[1] < n_q}
    ref_sub = reference.DedupReference(
        sure={p for p in ref.sure if p[1] < n_q},
        cut={p for p in ref.cut if p[1] < n_q}, pairs_examined=0,
        candidates=0, candidates_above=0)
    assert len(ref_sub.sure) > 20
    assert reference.compare(got, ref_sub) == (0, 0)
    assert reference.compare(sub, ref_sub) == (0, 0)


@pytest.mark.usefixtures("restore_jax_config")
def test_control_is_not_correct(tmp_path):
    """The control, the reference with its stage-1 cosine in bfloat16 put
    in ``run_er``'s place, comes out of a whole ``ds2_dedup`` run as not
    correct at 43,437 records (1,390,000 / 32), where pairs that pass
    stage 2 lie within bfloat16's reach of the cut; a sound run of the
    same cell is correct."""
    import control
    import run
    base = benchkit.tiny_tree(tmp_path, n=43_437)
    argv = ["--workload", "ds2_dedup", "--seed", "1", "--seconds", "0"]
    conf = json.loads((tmp_path / "bench/configs/ds2_publications.json")
                      .read_text())
    res = control.dedup_control(argv, conf, require=benchkit.on_cpu,
                                base=base)
    assert res["correct"] is False
    assert res["checks"]["mismatched_pairs"]["value"] >= 1


def test_corpus_and_schedule_follow_the_seed():
    from kinds.serve import schedule
    a, b = build_corpus(DS2, 2 ** 33 + 5), build_corpus(DS2, 2 ** 33 + 5)
    assert a.titles == b.titles
    assert build_corpus(DS2, 6).titles != a.titles
    mix = json.loads((benchkit.BENCH / "traffic" / "serve_open_zipf.json")
                     .read_text())
    s1, s2 = schedule(mix, a, 7, 3.0), schedule(mix, a, 7, 3.0)
    s3 = schedule(mix, a, 8, 3.0)
    assert s1.titles == s2.titles and np.array_equal(s1.at, s2.at)
    # every seed: the same gaps and sizes, in its own order
    def gaps(s):
        return np.sort(np.diff(np.r_[s.at, 3.0]))
    assert np.allclose(gaps(s1), gaps(s3))
    assert not np.array_equal(s1.at, s3.at)
    assert sorted(s1.sizes) == sorted(s3.sizes)
    assert all(t[:a.prefix_len] in {x[:a.prefix_len] for x in a.titles}
               for t in s1.titles)
