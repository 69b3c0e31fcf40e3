#!/usr/bin/env python3
"""Sweep the request rate of a serve cell's mix to find its knee.

    python3 bench/sweep_knee.py --workload ds1_serve_zipf --seed 1 \\
        --seconds 15 --rates 20,40,80,160

One service, warmed up once, takes the cell's mix at each rate in turn
for ``--seconds``, open loop, and the answers are drained before the
next rate. A rate is sustained when every request is answered and the
queue does not grow: the median latency of the last quarter of the
requests stays within 1.25 times that of the first quarter (5 ms
allowed for jitter). The sweep stops
at the first rate that is not sustained. The knee is the highest
sustained rate; a cell below it offers about four fifths of it,
written into its traffic file as ``rate_per_s``. Prints one line per
rate and, last, a JSON line with all of them. Needs the chip, like
``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run as harness


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated requests per second")
    args = p.parse_args(argv)
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(spec, args.workload, args.seed, args.seconds,
                             False)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(cell.root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        harness.require_chip(cell.chips)
    except harness.NoChip as e:
        harness.log(f"sweep: {e}; nothing was run")
        return harness.EXIT_NO_CHIP
    serve = harness.load_module("kinds", cell.traffic["kind"])
    from corpus import build_corpus
    from repro.er import ERBatcher, ERService
    corpus = build_corpus(cell.config, cell.seed)
    svc = ERService(corpus.titles, serve.service_config(cell.config))
    svc.warmup()
    rows = []
    with ERBatcher(svc) as batcher:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = dict(cell.traffic, rate_per_s=rate)
            sched = serve.schedule(mix, corpus, cell.seed + k, args.seconds)
            d = serve.drive(batcher, sched, args.seconds)
            out = serve.collect(sched, d, float(mix["drain_s"]))
            lat = out["latency_s"]
            q = max(1, lat.size // 4)
            first, last = float(np.median(lat[:q])), float(np.median(lat[-q:]))
            row = {"rate_per_s": rate, "requests": int(sched.n),
                   "titles": len(sched.titles),
                   "unanswered": out["failed"],
                   "p50_ms": 1e3 * serve.percentile(lat, 50),
                   "p95_ms": 1e3 * serve.percentile(lat, 95),
                   "first_quarter_p50_ms": 1e3 * first,
                   "last_quarter_p50_ms": 1e3 * last,
                   "titles_per_s_in_window": out["titles_in_window"]
                   / args.seconds,
                   "late_max_s": float(d["late"].max())}
            row["sustained"] = bool(out["failed"] == 0
                                    and last <= 1.25 * first + 0.005)
            rows.append(row)
            print(json.dumps(row), flush=True)
            if not row["sustained"]:
                break
            time.sleep(1.0)
    knee = max((r["rate_per_s"] for r in rows if r["sustained"]),
               default=None)
    print(json.dumps({"knee_per_s": knee, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
