"""Sorted Neighborhood dedup: whole ``run_er`` jobs back to back at the
configuration's window, on a data mesh of the cell's chips
(``repro.sharding.make_er_mesh``).

The jobs run as in the ``dedup`` kind: one warm-up job before the window,
each job on the corpus in a fresh record order drawn from the seed, the
window ending with the first job that finishes after ``--seconds``, and
one job profiled in a traced run. The record is a ``dedup`` record, so
the ``.dedup`` metric readers apply; each job also notes the bytes each
device received over the interconnect, by flow, from the program's
``stage1_stats["interconnect"]`` counter where it has one.

SN's band depends on the record order where sort keys tie, so every
job's full match set is compared with ``bench/sn_reference.py`` on that
job's own order.
"""
from __future__ import annotations

import time
from typing import Dict, List

from corpus import build_corpus, seed_rng
import reference
import sn_reference

__all__ = ["run", "release", "check", "er_config"]


def er_config(config: dict):
    """The ``ERConfig`` a configuration states; every other field keeps
    the program's default."""
    from repro.er import ERConfig
    m = config["matcher"]
    return ERConfig(strategy=config["strategy"],
                    window=int(config["window"]), r=int(config["r"]),
                    m=int(config["m"]), threshold=float(m["threshold"]),
                    filter_margin=float(m["filter_margin"]),
                    feature_dim=int(m["feature_dim"]),
                    max_len=int(m["max_len"]))


def _counters() -> Dict[str, int]:
    """Stage-1 survivors and interconnect bytes per flow so far."""
    from repro.er import compiler
    stats = getattr(compiler, "stage1_stats", None) or {}
    out = {"survivors": int(stats.get("survivors", 0)),
           "compact_overflows": int(stats.get("compact_overflows", 0))}
    for flow, v in (stats.get("interconnect") or {}).items():
        out[flow] = int(v)
    return out


def run(cell, h) -> Dict:
    from repro.er import run_er
    corpus = build_corpus(cell.config, cell.seed)
    cfg = er_config(cell.config)
    mesh = None
    if cell.chips > 1:
        from repro.sharding import make_er_mesh
        mesh = make_er_mesh(cell.chips)
    orders = seed_rng(cell.seed, 1)

    def job() -> Dict:
        perm = orders.permutation(corpus.n)
        titles = [corpus.titles[i] for i in perm]
        before = _counters()
        c0, t0 = time.process_time(), time.perf_counter()
        with h.span("job"):
            res = run_er(titles, cfg, mesh=mesh)
        seconds = time.perf_counter() - t0
        moved = {k: v - before.get(k, 0) for k, v in _counters().items()}
        return {"perm": perm, "matches": res.matches, "seconds": seconds,
                "cpu_s": time.process_time() - c0,
                "live_pairs": int(res.total_pairs),
                "tiles": res.extra.get("catalog_tiles"),
                "survivors": moved.pop("survivors"),
                "compact_overflows": moved.pop("compact_overflows"),
                "interconnect": moved}

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
    sem = sn_reference.semantics(cell.config)
    t0 = time.perf_counter()
    ref = sn_reference.SNReference(corpus.titles, int(cell.config["window"]),
                                   sem)
    total, sure = 0, []
    for j in rec["jobs"]:
        perm = j["perm"]
        want = ref.job(perm)
        sure.append(len(want.sure))
        got = {(min(int(perm[a]), int(perm[b])),
                max(int(perm[a]), int(perm[b]))) for a, b in j["matches"]}
        missing, extra = reference.compare(got, want)
        total += missing + extra
        for kind, pairs in (("missing", want.sure - got),
                            ("extra", got - want.sure - want.cut)):
            for row in reference.explain(list(pairs)[:10], corpus.titles,
                                         corpus.titles, sem):
                print(f"bench: {kind} pair (a, b, cosine, edit distance, "
                      f"lengths): {row}", flush=True)
    jobs = rec["jobs"]
    print(f"bench: {len(jobs)} jobs in {rec['window_s']:.6f} s, job "
          f"seconds {[round(j['seconds'], 6) for j in jobs]}, process CPU "
          f"seconds {[round(j['cpu_s'], 3) for j in jobs]}, tiles "
          f"{[j['tiles'] for j in jobs]}, backend compiles in the window "
          f"{rec['compiles_in_window']}", flush=True)
    print(f"bench: per job, stage-1 survivors {[j['survivors'] for j in jobs]}"
          f", compact overflows {[j['compact_overflows'] for j in jobs]}, "
          f"interconnect bytes per device {[j['interconnect'] for j in jobs]}",
          flush=True)
    print(f"bench: SN reference, window {ref.w}, {ref.n} records of which "
          f"{int(ref.tied.sum())} hold a tied key, sure matches per job "
          f"{sure}, {len(want.cut) if jobs else 0} pairs within {sem.band:g} "
          f"of the stage-1 cut that pass stage 2, in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    return [{"name": "mismatched_pairs", "value": total, "limit": 0}]
