"""Batch dedup: whole ``run_er`` jobs back to back on the cell's corpus.

One job, run before the window, is the warm-up. A cell on more than one
chip runs ``run_er`` on ``repro.sharding.make_er_mesh(chips)``. Each job
gets the corpus in a fresh record order drawn from the seed, so no job
can reuse another's answers; the match set of a reordered corpus is the
reordered match set, so one reference run on the corpus as built checks
every job.

The window runs from the first job after warm-up to the end of the first
job that finishes after ``--seconds``; a traced run profiles exactly one
job. Every job's full match set is compared with the reference. Each job
also notes the host's share of its wall time (process CPU seconds, time
in Python's collector, involuntary context switches), printed on an
earlier line, to tell the program's own work from pauses around it.
"""
from __future__ import annotations

import resource
import time
from typing import Dict, List

from corpus import build_corpus, seed_rng
import reference

__all__ = ["run", "release", "check", "er_config"]


def er_config(config: dict):
    """The ``ERConfig`` a configuration states; every other field keeps
    the program's default."""
    from repro.er import ERConfig
    m = config["matcher"]
    return ERConfig(strategy=config["strategy"], r=int(config["r"]),
                    m=int(config["m"]), prefix_len=int(config["prefix_len"]),
                    threshold=float(m["threshold"]),
                    filter_margin=float(m["filter_margin"]),
                    feature_dim=int(m["feature_dim"]),
                    max_len=int(m["max_len"]))


def _survivors() -> int:
    """The program's stage-1 survivor counter, where it has one."""
    from repro.er import compiler
    stats = getattr(compiler, "stage1_stats", None)
    return int(stats.get("survivors", 0)) if stats else 0


def run(cell, h) -> Dict:
    from repro.er import run_er
    corpus = build_corpus(cell.config, cell.seed)
    if corpus.prefix_len != int(cell.config["prefix_len"]):
        raise ValueError(f"the corpus needs prefix width "
                         f"{corpus.prefix_len}, the configuration states "
                         f"{cell.config['prefix_len']}")
    cfg = er_config(cell.config)
    mesh = None
    if cell.chips > 1:
        from repro.sharding import make_er_mesh
        mesh = make_er_mesh(cell.chips)
    orders = seed_rng(cell.seed, 1)

    def job() -> Dict:
        perm = orders.permutation(corpus.n)
        titles = [corpus.titles[i] for i in perm]
        s0, gc0 = _survivors(), h.gc_s
        nivcsw0 = resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
        c0, t0 = time.process_time(), time.perf_counter()
        with h.span("job"):
            res = run_er(titles, cfg, mesh=mesh)
        return {"perm": perm, "matches": res.matches,
                "seconds": time.perf_counter() - t0,
                "cpu_s": time.process_time() - c0,
                "gc_s": h.gc_s - gc0,
                "nivcsw": resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
                - nivcsw0,
                "live_pairs": int(res.total_pairs),
                "tiles": res.extra.get("catalog_tiles"),
                "survivors": _survivors() - s0}

    job()
    h.window_opens()
    compiles0 = h.compiles
    jobs: List[Dict] = []
    if cell.trace:
        with h.profile():
            t0 = time.perf_counter()
            jobs.append(job())
            window = time.perf_counter() - t0
    else:
        t0 = time.perf_counter()
        while True:
            jobs.append(job())
            window = time.perf_counter() - t0
            if window >= cell.seconds:
                break
    return {"kind": "dedup", "corpus": corpus, "jobs": jobs,
            "window_s": window, "records": corpus.n,
            "compiles_in_window": h.compiles - compiles0,
            "feature_dim": cfg.feature_dim,
            "attempted": len(jobs), "failed": 0}


def release(rec: Dict) -> None:
    """Nothing of the program outlives a job."""


def check(cell, rec: Dict) -> List[Dict]:
    corpus = rec["corpus"]
    sem = reference.Semantics.of(cell.config)
    t0 = time.perf_counter()
    ref = reference.dedup_reference(corpus.titles, sem)
    total = 0
    for j in rec["jobs"]:
        perm = j["perm"]
        got = {(min(int(perm[a]), int(perm[b])),
                max(int(perm[a]), int(perm[b]))) for a, b in j["matches"]}
        missing, extra = reference.compare(got, ref)
        total += missing + extra
        for kind, pairs in (("missing", ref.sure - got),
                            ("extra", got - ref.sure - ref.cut)):
            for row in reference.explain(list(pairs)[:10], corpus.titles,
                                         corpus.titles, sem):
                print(f"bench: {kind} pair (a, b, cosine, edit distance, "
                      f"lengths): {row}", flush=True)
    s = [j["survivors"] for j in rec["jobs"]]
    print(f"bench: {len(rec['jobs'])} jobs in {rec['window_s']:.6f} s, "
          f"job seconds {[round(j['seconds'], 6) for j in rec['jobs']]}, "
          f"tiles {[j['tiles'] for j in rec['jobs']]}, backend compiles in "
          f"the window {rec['compiles_in_window']}", flush=True)
    print(f"bench: per job, process CPU seconds "
          f"{[round(j['cpu_s'], 3) for j in rec['jobs']]}, seconds in Python's "
          f"collector {[round(j['gc_s'], 3) for j in rec['jobs']]}, "
          f"involuntary context switches {[j['nivcsw'] for j in rec['jobs']]}",
          flush=True)
    print(f"bench: reference {len(ref.sure)} sure matches over "
          f"{ref.pairs_examined} same-key pairs in "
          f"{time.perf_counter() - t0:.3f} s; stage-1 survivors per job "
          f"{s} against the reference's {ref.candidates_above} to "
          f"{ref.candidates}", flush=True)
    print(f"bench: pairs within {sem.band:g} of the stage-1 cut that pass "
          f"stage 2: {len(ref.cut)}", flush=True)
    return [{"name": "mismatched_pairs", "value": total, "limit": 0}]
