"""The program's host spans and the per-layer metrics that read them."""
from __future__ import annotations

import math

import pytest

import benchkit  # noqa: F401  (puts bench/ and src/ on the path)
import run
import spans
import xplane
from spans import Span
from xplane import Event, Trace

PHASES = ("er.featurize", "er.block", "er.bdm", "er.plan", "er.job",
          "er.lower", "er.schedule", "er.stage1", "er.stage2", "er.collect")


def test_run_er_phases_on_the_cpu(tmp_path):
    """A traced ``run_er`` at 3,000 records: one span per phase, side by
    side under ``er.run_er`` and covering it, and per-chunk spans that
    count the launches, the stage-2 chunks and the survivors."""
    import jax
    from repro.er import ERConfig, compiler, make_products, run_er

    ds = make_products(3000, seed=0)
    before = dict(compiler.stage1_stats)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = run_er(ds.titles, ERConfig(r=100, m=20))
    finally:
        jax.profiler.stop_trace()
    survivors = compiler.stage1_stats["survivors"] - before["survivors"]
    assert compiler.stage1_stats["compact_overflows"] \
        == before["compact_overflows"]
    sp = spans.load(xplane.newest_xplane(str(tmp_path)))

    (root,) = [s for s in sp if s.name == "er.run_er"]
    assert root.path == () and root.arg("n") == ds.n
    phases = [s for s in sp if s.path == ("er.run_er",)]
    names = [s.name for s in phases]
    expected = PHASES + (("er.null_key",) if "null_key_pairs" in res.extra
                         else ())
    assert sorted(names) == sorted(expected)
    for a, b in zip(phases, phases[1:]):
        assert a.end <= b.start
    assert sum(s.seconds for s in phases) >= 0.95 * root.seconds

    def under(parent, name):
        return [s for s in sp if s.name == name and s.path[-1:] == (parent,)]

    tiles = res.extra["catalog_tiles"]
    launches = under("er.stage1", "er.stage1.launch")
    assert len(launches) == math.ceil(tiles / 1024)
    assert sum(s.arg("tiles") for s in launches) == tiles
    assert len(under("er.stage1", "er.stage1.upload")) == 1
    main = sum(s.arg("survivors") for s in under("er.stage1",
                                                 "er.stage1.decode"))
    (stage2,) = under("er.run_er", "er.stage2")
    assert stage2.arg("pairs") == main
    assert len(under("er.stage2", "er.stage2.sync")) == math.ceil(main / 8192)
    assert sum(s.arg("pairs") for s in under("er.stage2", "er.stage2.gather")
               ) == main
    every = [s.arg("survivors") for s in sp if s.name == "er.stage1.decode"]
    assert sum(every) == survivors


# -- the six readers on a synthetic trace ----------------------------------

def _span(name, start, end, *path, **args):
    return Span(name, start, end, tuple(path), tuple(args.items()))


def synthetic_spans():
    """One job, as ``run_er`` nests its spans; ``er.featurize`` starts
    before the window [0, 10] and nothing is open in [4.75, 5.0]."""
    r = "er.run_er"
    s1, s2 = (r, "er.stage1"), (r, "er.stage2")
    return [
        _span(r, -1.0, 9.5),
        _span("er.featurize", -1.0, 2.0, r),
        _span("er.block", 2.0, 2.5, r),
        _span("er.bdm", 2.5, 2.7, r),
        _span("er.plan", 2.7, 3.0, r),
        _span("er.job", 3.0, 4.0, r),
        _span("er.lower", 4.0, 4.5, r),
        _span("er.schedule", 4.5, 4.75, r),
        _span("er.stage1", 5.0, 7.0, r),
        _span("er.stage1.upload", 5.0, 5.1, *s1),
        _span("er.stage1.launch", 5.1, 5.2, *s1, tiles=1024, padded=1024),
        _span("er.stage1.sync", 5.2, 6.0, *s1),
        _span("er.stage1.decode", 6.0, 6.3, *s1, survivors=9000),
        _span("er.stage1.launch", 6.3, 6.4, *s1, tiles=5, padded=8),
        _span("er.stage1.sync", 6.4, 6.8, *s1),
        _span("er.stage1.decode", 6.8, 7.0, *s1, survivors=2),
        _span("er.stage2", 7.0, 9.0, r, pairs=9002),
        _span("er.stage2.gather", 7.0, 7.2, *s2, pairs=8192),
        _span("er.stage2.sync", 7.2, 8.0, *s2),
        _span("er.stage2.gather", 8.0, 8.1, *s2, pairs=810),
        _span("er.stage2.sync", 8.1, 8.9, *s2),
        _span("er.collect", 9.0, 9.4, r),
    ]


def synthetic_trace() -> Trace:
    ops = {0: [Event("%pair_scores_catalog_compact.1", 5.15, 5.9),
               Event("%pair_scores_catalog_compact.1", 6.35, 6.75),
               Event("%while.1", 7.3, 7.9), Event("%while.1", 8.2, 8.85),
               Event("%copy.1", 9.6, 9.8)]}
    return Trace(devices=ops, spans=[Event("bench.window", 0.0, 10.0)],
                 t0=0.0, t1=10.0)


# Milliseconds per job over two jobs. Unattributed: the window less
# [0, 4.75] and [5.0, 9.4] (spans) and [9.6, 9.8] (an op) = 0.65 s.
@pytest.mark.parametrize("metric,per_job_ms", [
    ("featurize_ms.dedup", 1000.0),         # clipped at the window: 2.0 s
    ("plan_ms.dedup", 500.0),               # 0.5 + 0.2 + 0.3
    ("lower_ms.dedup", 875.0),              # 1.0 + 0.5 + 0.25
    ("survivor_decode_ms.dedup", 250.0),    # 0.3 + 0.2
    ("stage2_host_ms.dedup", 200.0),        # 2.0 - 0.8 - 0.8
    ("idle_unattributed_ms.dedup", 325.0),
])
def test_span_readers(monkeypatch, metric, per_job_ms):
    read = run.load_module("metrics", metric).read
    rec = {"kind": "dedup", "jobs": [{}, {}], "trace": synthetic_trace(),
           "cell": "synthetic", "chips": 1}
    found = synthetic_spans()
    monkeypatch.setattr(spans, "newest_xplane", lambda d: "synthetic.pb")
    monkeypatch.setattr(spans, "load", lambda path: found)
    assert read(rec) == pytest.approx(per_job_ms)
    # a program that opens no spans reads as nothing, and so does an
    # untraced run
    found = [s for s in found if s.name != "er.run_er"]
    assert read(rec) is None
    assert read(dict(rec, trace=None)) is None


# -- a chip trace ----------------------------------------------------------

CHIP_TRACE = str(benchkit.BENCH / "tests" / "data"
                 / "run_er_3000_spans.xplane.pb.gz")


def test_chip_trace_spans(monkeypatch):
    """A trace recorded on a TPU v5e: two ``run_er`` jobs over 3,000
    titles (1,029 tiles: a 1,024-tile and a 5-tile stage-1 launch, and
    one stage-2 chunk, per job) inside ``bench.window``. The readers'
    numbers were read from the raw events with
    ``jax.profiler.ProfileData`` when the trace was recorded."""
    tr = xplane.load(CHIP_TRACE)
    sp = spans.load(CHIP_TRACE)
    assert tr.window_s == pytest.approx(0.564593, abs=1e-6)
    roots = [s for s in sp if s.name == "er.run_er"]
    assert len(roots) == 2
    for root in roots:
        inside = [s.name for s in sp if s.path == ("er.run_er",)
                  and root.start <= s.start < root.end]
        assert inside == list(PHASES)

    # Host and device share the profiler's clock: each kernel runs
    # between its launch and the end of the sync that waits for it, and
    # each stage-2 program inside its sync, to within 1 ms.
    kernels = [e for e in tr.ops(0) if e.name.startswith("%pair_scores")]
    launches = [s for s in sp if s.name == "er.stage1.launch"]
    syncs = [s for s in sp if s.name == "er.stage1.sync"]
    assert len(kernels) == len(launches) == len(syncs) == 4
    for k, launch, sync in zip(kernels, launches, syncs):
        assert launch.start - 1e-3 <= k.start and k.end <= sync.end
    edits = [e for e in tr.ops(0, modules=True)
             if e.name.startswith("jit_edit_distance(")]
    s2 = [s for s in sp if s.name == "er.stage2.sync"]
    assert len(edits) == len(s2) == 2
    for e, sync in zip(edits, s2):
        assert sync.start - 1e-3 <= e.start and e.end <= sync.end

    rec = {"kind": "dedup", "jobs": [{}, {}], "trace": tr, "cell": "chip",
           "chips": 1}
    monkeypatch.setattr(spans, "newest_xplane", lambda d: CHIP_TRACE)
    read = {m: run.load_module("metrics", m).read(rec) for m in (
        "featurize_ms.dedup", "plan_ms.dedup", "lower_ms.dedup",
        "survivor_decode_ms.dedup", "stage2_host_ms.dedup",
        "idle_unattributed_ms.dedup")}
    assert read == pytest.approx({
        "featurize_ms.dedup": 13.862869, "plan_ms.dedup": 99.613789,
        "lower_ms.dedup": 131.597686, "survivor_decode_ms.dedup": 0.172525,
        "stage2_host_ms.dedup": 0.641905,
        "idle_unattributed_ms.dedup": 0.179268}, abs=1e-5)
    idle_ms = 1e3 * (tr.window_s - xplane.busy_s(tr, 0)) / 2
    assert read["idle_unattributed_ms.dedup"] <= 0.05 * idle_ms
