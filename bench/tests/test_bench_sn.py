"""The Sorted Neighborhood cell on the CPU: its reference against the
program, and whole ``ds2_sn_mesh4`` runs through ``run.py`` on four
simulated devices, sound and broken."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import benchkit  # noqa: F401  (puts bench/ and src/ on the path)
from corpus import build_corpus, seed_rng
import reference
import sn_reference

DS2 = dict(n_records=2500, head_frac=0.04, pair_share=0.26, dup_frac=0.03)


def with_ties(titles, seed):
    """The titles plus a run of 40 near-identical ones, a tenth of them
    twice: matches at every distance of a small window, and tied keys."""
    run = [f"zzz laptop phone {v:04d}" for v in range(40)]
    titles = list(titles) + run + run[::10]
    perm = np.random.default_rng(seed).permutation(len(titles))
    return [titles[i] for i in perm]


@pytest.mark.parametrize("w", [37, 300])
def test_sn_reference_equals_run_er_in_every_order(w):
    """Every job order, ties included, gets the program's answer."""
    from repro.er import ERConfig, run_er
    titles = with_ties(build_corpus(DS2, 5).titles, 5)
    ref = sn_reference.SNReference(titles, w, reference.Semantics())
    assert ref.tied.sum() >= 8
    for s in (1, 2, 3):
        perm = seed_rng(s, 1).permutation(len(titles))
        res = run_er([titles[i] for i in perm],
                     ERConfig(strategy="sorted_neighborhood", window=w, r=20))
        got = {(min(int(perm[a]), int(perm[b])),
                max(int(perm[a]), int(perm[b]))) for a, b in res.matches}
        want = ref.job(perm)
        assert len(want.sure) > 40
        assert want.pairs_examined == res.total_pairs
        assert reference.compare(got, want) == (0, 0)


def test_sn_reference_band_is_every_pair_within_the_window():
    """At a window as wide as the corpus the band is the whole triangle,
    and the matches are those of a plain all-pairs loop."""
    titles = with_ties(build_corpus(DS2, 7).titles[:300], 7)
    sem = reference.Semantics()
    want = sn_reference.SNReference(titles, len(titles), sem).job()
    codes, lens, counts, sq = reference.featurize(titles, sem)
    a, b = np.triu_indices(len(titles), k=1)
    dot = (counts[a].astype(np.int64) * counts[b].astype(np.int64)).sum(1)
    cos = reference._cos(dot, sq[a], sq[b])
    hit = cos >= sem.cut - sem.band
    a, b, cos = a[hit], b[hit], cos[hit]
    dist = reference.edit_distance(codes[a], lens[a], codes[b], lens[b])
    passed = reference.edit_matches(dist, lens[a], lens[b], sem.threshold)
    sure, amb = reference._classify(a, b, cos, passed, sem)
    assert want.sure == sure and want.cut == amb
    assert want.pairs_examined == len(titles) * (len(titles) - 1) // 2


SN_RUN = textwrap.dedent("""
    import json
    import sys
    from dataclasses import replace
    from pathlib import Path
    sys.path.insert(0, {tests!r})
    from benchkit import on_cpu, tiny_tree
    import run
    base = tiny_tree(Path({tmp!r}), n={n})
    conf_path = base / "configs" / "ds2_sn_w1000.json"
    conf = json.loads(conf_path.read_text())
    if {window!r}:
        conf["window"] = {window!r}
        conf_path.write_text(json.dumps(conf))
        import corpus
        from test_bench_sn import with_ties
        real = corpus.build_corpus
        corpus.build_corpus = lambda p, s: corpus.Corpus(
            titles=with_ties(real(p, s).titles, s),
            prefix_len=real(p, s).prefix_len)
    argv = ["--workload", "ds2_sn_mesh4", "--seed", "2024", "--seconds",
            "0.1"]
    assert run.run(argv, require=on_cpu, base=base) == 0
    if {fault!r} == "window":
        # the band one sorted position short
        import repro.er
        real_run_er = repro.er.run_er
        repro.er.run_er = lambda titles, cfg, **k: real_run_er(
            titles, replace(cfg, window=cfg.window - 1), **k)
    elif {fault!r} == "ties":
        # records with equal keys sorted latest input first
        import numpy as np
        import repro.er.pipeline as pipeline
        from repro.er.blocking import sn_sort_keys

        def later_first(titles):
            keys = np.asarray(sn_sort_keys(titles))[::-1]
            return len(titles) - 1 - np.argsort(keys, kind="stable")
        pipeline.sn_sort_order = later_first
    else:
        sys.exit(0)
    assert run.run(argv, require=on_cpu, base=base) == 0
""")


def _sn_run(tmp_path, n, window=None, fault=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = SN_RUN.format(tests=str(Path(__file__).parent),
                           tmp=str(tmp_path), n=n, window=window,
                           fault=fault)
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return [json.loads(line) for line in p.stdout.splitlines()
            if line.startswith("{")], p.stdout


def test_sn_cell_is_correct_through_the_halo(tmp_path):
    """The cell as configured (w = 1,000) at 2,000 records: the window is
    wider than a shard, so the halo crosses two hops."""
    (res,), log = _sn_run(tmp_path, n=2000)
    assert res["correct"] is True
    assert res["checks"]["mismatched_pairs"]["value"] == 0
    assert "'halo_bytes': 1022976" in log, log[-2000:]   # 999 rows · 1 KiB
    assert "'flat_bytes': 0" in log


@pytest.mark.parametrize("fault", ["window", "ties"],
                         ids=["window-one-short", "ties-against-the-rule"])
def test_broken_sn_run_is_not_correct(tmp_path, fault):
    """With a run of near-identical titles at a window of 8, a band one
    position short, or ties broken against the stated rule, misses or
    adds pairs; the same run unbroken is correct."""
    (sound, broken), _ = _sn_run(tmp_path, n=1500, window=8, fault=fault)
    assert sound["correct"] is True
    assert broken["correct"] is False
    assert broken["checks"]["mismatched_pairs"]["value"] > 0
