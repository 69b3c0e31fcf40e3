"""Open-loop match traffic against a resident ``ERService``.

An ``ERService`` holds the cell's corpus; requests go through
``ERBatcher.submit`` at the times of a fixed schedule, whether or not
earlier ones have finished. The traffic file gives:

    rate_per_s            mean request rate (the cell's fixed load)
    titles_min/_max       titles per request, every size equally often
    zipf_theta            YCSB zipfian skew over a seeded record order
    reshuffle_s           the popularity order is redrawn this often
    trace_seconds         length of a traced run's window
    drain_s               how long answers may come after the window

Every seed gets the same set of gaps and request sizes, in its own
order: the gaps are the quantiles of the exponential distribution at the
rate, so arrivals are Poisson-like and a run's load does not move with
the seed. Each title is a corpus record drawn by the zipfian
distribution, then given one or two character edits past its blocking
key (the corpus generator's ``perturb``), so no title lacks a key.

Latency runs from a request's scheduled time to the resolution of its
future; a request that fails or is not answered within ``drain_s`` after
the window counts as late as that wait, beyond any limit, and makes the
run incorrect. Every answered request's match set
is compared with the reference once the window has closed.
"""
from __future__ import annotations

import math
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from corpus import build_corpus, perturb, seed_rng
import reference

__all__ = ["Schedule", "schedule", "zipf_ranks", "run", "release",
           "check", "drive", "service_config"]


def zipf_ranks(rng: np.random.Generator, n: int, theta: float,
               size: int) -> np.ndarray:
    """YCSB's ZipfianGenerator (Gray et al., "Quickly generating
    billion-record synthetic databases"): ranks in [0, n), rank 0 the
    most popular."""
    zetan = float(np.sum(1.0 / np.arange(1, n + 1, dtype=np.float64)
                         ** theta))
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(size)
    uz = u * zetan
    ranks = (n * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ranks = np.where(uz < 1.0 + 0.5 ** theta, 1, ranks)
    ranks = np.where(uz < 1.0, 0, ranks)
    return np.clip(ranks, 0, n - 1)


@dataclass
class Schedule:
    at: np.ndarray            # (N,) seconds after the window opens
    sizes: np.ndarray         # (N,) titles per request
    titles: List[str]         # all titles, request after request
    offsets: np.ndarray       # (N,) first title of each request

    @property
    def n(self) -> int:
        return int(self.at.size)


def schedule(mix: dict, corpus, seed: int, seconds: float) -> Schedule:
    rate = float(mix["rate_per_s"])
    n_req = max(1, int(round(rate * seconds)))
    rng = seed_rng(seed, 2)
    q = (np.arange(n_req) + 0.5) / n_req
    gaps = -np.log1p(-q) / rate
    rng.shuffle(gaps)
    gaps *= seconds / gaps.sum()
    at = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    lo, hi = int(mix["titles_min"]), int(mix["titles_max"])
    sizes = np.resize(np.arange(lo, hi + 1), n_req)
    rng.shuffle(sizes)
    offsets = np.concatenate([[0], np.cumsum(sizes[:-1])]).astype(np.int64)
    total = int(sizes.sum())
    # popularity: a seeded record order per reshuffle period
    epoch = np.repeat((at // float(mix["reshuffle_s"])).astype(np.int64),
                      sizes)
    ranks = zipf_ranks(rng, corpus.n, float(mix["zipf_theta"]), total)
    records = np.empty(total, np.int64)
    for e in np.unique(epoch):
        order = seed_rng(seed, 3, int(e)).permutation(corpus.n)
        sel = epoch == e
        records[sel] = order[ranks[sel]]
    edits = seed_rng(seed, 4)
    titles = [perturb(edits, corpus.titles[int(r)], keep=corpus.prefix_len)
              for r in records]
    return Schedule(at=at, sizes=sizes, titles=titles, offsets=offsets)


def service_config(config: dict):
    """The ``ServiceConfig`` a configuration states; every other field
    keeps the program's default."""
    from repro.er import ServiceConfig
    m = config["matcher"]
    return ServiceConfig(strategy=config["strategy"], r=int(config["r"]),
                         m=int(config["m"]),
                         prefix_len=int(config["prefix_len"]),
                         threshold=float(m["threshold"]),
                         filter_margin=float(m["filter_margin"]),
                         feature_dim=int(m["feature_dim"]),
                         max_len=int(m["max_len"]))


def drive(batcher, sched: Schedule, seconds: float, h=None) -> Dict:
    """Submit every request at its time and return at the window's
    close: the futures, their resolution times, how late each submit
    ran, and the window's bounds. With a harness ``h``, the waits
    between arrivals and the submits are host spans of the trace."""
    n = sched.n
    done = np.full(n, np.inf)
    noted = [threading.Event() for _ in range(n)]
    late = np.zeros(n)
    futures = []

    def on_done(i):
        def cb(_):
            done[i] = time.perf_counter()
            noted[i].set()
        return cb

    def sleep_until(t):
        wait = t - time.perf_counter()
        if wait > 0:
            with span("wait"):
                time.sleep(wait)

    span = h.span if h is not None else (lambda name: nullcontext())
    t0 = time.perf_counter()
    for i in range(n):
        due = t0 + sched.at[i]
        sleep_until(due)
        late[i] = time.perf_counter() - due
        lo = int(sched.offsets[i])
        with span("submit"):
            fut = batcher.submit(sched.titles[lo:lo + int(sched.sizes[i])])
        fut.add_done_callback(on_done(i))
        futures.append(fut)
    close = t0 + seconds
    sleep_until(close)
    return {"t0": t0, "close": close, "futures": futures, "done": done,
            "noted": noted, "late": late}


def collect(sched: Schedule, d: Dict, drain_s: float) -> Dict:
    """Wait for the answers after the window; latencies and answers."""
    end = d["close"] + drain_s
    answers: List = [None] * sched.n
    failed = 0
    for i, fut in enumerate(d["futures"]):
        try:
            answers[i] = fut.result(timeout=max(0.0, end - time.perf_counter()))
        except Exception:           # a failed or unanswered request
            failed += 1
            continue
        d["noted"][i].wait(1.0)     # the callback that timed it
    due = d["t0"] + sched.at
    ok = np.array([a is not None for a in answers])
    # an unanswered request is as late as the wait for it, at least
    lat = np.where(ok, d["done"] - due, end - due)
    in_window = ok & (d["done"] <= d["close"])
    return {"answers": answers, "latency_s": lat, "failed": failed,
            "titles_in_window": int(sched.sizes[in_window].sum())}


def run(cell, h) -> Dict:
    from repro.er import ERBatcher, ERService
    mix = cell.traffic
    corpus = build_corpus(cell.config, cell.seed)
    if corpus.prefix_len != int(cell.config["prefix_len"]):
        raise ValueError("the corpus's prefix width differs from the "
                         "configuration's")
    seconds = float(mix["trace_seconds"]) if cell.trace else cell.seconds
    sched = schedule(mix, corpus, cell.seed, seconds)
    svc = ERService(corpus.titles, service_config(cell.config))
    svc.warmup()
    batcher = ERBatcher(svc)
    before = _stats(svc)
    h.window_opens()
    compiles0 = h.compiles
    if cell.trace:
        with h.profile():
            d = drive(batcher, sched, seconds, h)
        after_trace = _stats(svc)
    else:
        d = drive(batcher, sched, seconds, h)
        after_trace = None
    out = collect(sched, d, float(mix["drain_s"]))
    after = _stats(svc)
    if not out["failed"]:
        batcher.close()             # else its daemon threads end with us
    stats = _delta(before, after_trace or after)
    return {"kind": "serve", "corpus": corpus, "schedule": sched,
            "window_s": seconds, "latency_s": out["latency_s"],
            "answers": out["answers"], "late_s": d["late"],
            "titles_in_window": out["titles_in_window"],
            "service_stats": stats,
            "compiles_in_window": h.compiles - compiles0,
            "program": [svc, batcher],
            "attempted": sched.n, "failed": out["failed"]}


def _stats(svc) -> Dict:
    s = svc.stats
    return {"batches": s["batches"], "queries": s["queries"],
            "seconds": s["seconds"], "bucket_hits": dict(s["bucket_hits"])}


def _delta(a: Dict, b: Dict) -> Dict:
    hits = {k: b["bucket_hits"][k] - a["bucket_hits"].get(k, 0)
            for k in b["bucket_hits"]}
    return {"batches": b["batches"] - a["batches"],
            "queries": b["queries"] - a["queries"],
            "seconds": b["seconds"] - a["seconds"],
            "slots": sum(int(k) * v for k, v in hits.items()),
            "bucket_hits": hits}


def release(rec: Dict) -> None:
    """Drop the service, whose corpus features live on the device."""
    rec.pop("program", None)


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, math.ceil(q / 100.0 * v.size) - 1)])


def check(cell, rec: Dict) -> List[Dict]:
    sched: Schedule = rec["schedule"]
    corpus = rec["corpus"]
    sem = reference.Semantics.of(cell.config)
    t0 = time.perf_counter()
    ref = reference.cross_reference(corpus.titles, sched.titles, sem)
    got = set()
    answered = np.zeros(len(sched.titles), bool)
    for i, ans in enumerate(rec["answers"]):
        if ans is None:
            continue
        lo = int(sched.offsets[i])
        answered[lo:lo + int(sched.sizes[i])] = True
        got.update((int(a), lo + int(b)) for a, b in ans)
    sure = {p for p in ref.sure if answered[p[1]]}
    cut = {p for p in ref.cut if answered[p[1]]}
    sub = reference.DedupReference(sure=sure, cut=cut,
                                   pairs_examined=ref.pairs_examined,
                                   candidates=ref.candidates,
                                   candidates_above=ref.candidates_above)
    missing, extra = reference.compare(got, sub)
    for kind, pairs in (("missing", sure - got), ("extra", got - sure - cut)):
        for row in reference.explain(list(pairs)[:10], corpus.titles,
                                     sched.titles, sem):
            print(f"bench: {kind} pair (corpus, query, cosine, edit "
                  f"distance, lengths): {row}", flush=True)
    late = rec["late_s"]
    print(f"bench: {sched.n} requests, {len(sched.titles)} titles in "
          f"{rec['window_s']:g} s; generator lateness max "
          f"{float(late.max()):.6f} s, p95 {percentile(late, 95):.6f} s; "
          f"backend compiles in the window {rec['compiles_in_window']}; "
          f"service {rec['service_stats']}", flush=True)
    print(f"bench: reference {len(ref.sure)} sure matches over "
          f"{ref.pairs_examined} same-key pairs in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    print(f"bench: pairs within {sem.band:g} of the stage-1 cut that pass "
          f"stage 2: {len(ref.cut)}", flush=True)
    return [{"name": "mismatched_pairs", "value": missing + extra,
             "limit": 0},
            {"name": "unanswered_requests", "value": rec["failed"],
             "limit": 0}]
