#!/usr/bin/env python3
"""Run the control of a cell's correctness check on a few seeds.

    python3 bench/control.py --workload ds1_dedup --seconds 1 --seeds 1,2,3

The control is the reference put in the program's place with its stage-1
cosine computed in bfloat16 (float32 accumulation), the precision below
the float32 features the configuration states. For a dedup cell it
stands in for ``run_er`` and the whole run goes through ``run.py``: the
cell's corpus at its own size, the warm-up job, the window's jobs, and
the cell's own check, which has to print ``correct: false``. For each
seed it prints one JSON line with ``correct`` and the numbers compared.
A serve cell's control prints the number the check would compare. The
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import time
from types import SimpleNamespace

import run as harness
import reference


def control_run_er(config: dict):
    """A stand-in for ``repro.er.run_er``: the control's match set of a
    self-join over ``titles``."""
    sem = reference.Semantics.of(config)

    def run_er(titles, cfg=None, mesh=None):
        ref = reference.dedup_reference(titles, sem, control=True)
        return SimpleNamespace(matches=ref.control,
                               total_pairs=ref.pairs_examined, extra={})
    return run_er


def dedup_control(argv, config: dict, require=harness.require_chip,
                  base=harness.BENCH) -> dict:
    """``run.run(argv)`` with the control in ``run_er``'s place; its
    result line. What the run printed before it goes to standard error."""
    import repro.er
    real = repro.er.run_er
    repro.er.run_er = control_run_er(config)
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            harness.run(argv, require=require, base=base)
    finally:
        repro.er.run_er = real
    *before, last = out.getvalue().strip().splitlines()
    for line in before:
        harness.log(line)
    return json.loads(last)


def serve_control(cell, seconds: float) -> dict:
    from corpus import build_corpus
    corpus = build_corpus(cell.config, cell.seed)
    sem = reference.Semantics.of(cell.config)
    serve = harness.load_module("kinds", "serve")
    sched = serve.schedule(cell.traffic, corpus, cell.seed, seconds)
    ref = reference.cross_reference(corpus.titles, sched.titles, sem,
                                    control=True)
    missing, extra = reference.compare(set(), ref, ref.control)
    return {"checks": {"mismatched_pairs": {"value": missing + extra,
                                            "limit": 0}},
            "near_cut_passing": len(ref.cut)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    args = p.parse_args(argv)
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.load_cell(spec, args.workload, seed, args.seconds,
                                 False)
        t0 = time.perf_counter()
        if cell.traffic["kind"] == "serve":
            res = serve_control(cell, args.seconds)
        else:
            res = dedup_control(["--workload", args.workload, "--seed",
                                 str(seed), "--seconds", str(args.seconds)],
                                cell.config)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": res.get("correct"),
                          "checks": res["checks"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
