"""Figs. 13/14 — scalability with the number of nodes n (DS1 and DS2).

Paper setup: m = 2n map tasks, r = 10n reduce tasks, n ∈ [1, 100].
Makespans are modeled from EXACT plan load distributions (the paper's
own balance metric) with a measured cost-per-pair:

    makespan(n) = max_k(load_k) · cost_per_pair / cores_per_node(2)
                  + bdm_overhead(n)

DS2 runs plan-math at full 1.39M-entity scale (5.6·10⁹ pairs — loads are
exact; no pair is materialized); cost_per_pair is measured on a DS1-
scale sample. Expected findings: Basic flatlines past 2 nodes; the
balanced strategies scale near-linearly until per-reducer work gets too
small (DS1 ~10 nodes, DS2 ~40 nodes); BlockSplit beats PairRange on
small datasets at large n (replication overhead), PairRange wins on DS2.

Every strategy row also carries the EXACT per-device interconnect bytes
each stage-1 gather policy would move at that node count
(``compiler.comms.comms_volume`` over the strategy's own lowered tile
catalog): the flat all-gather ships (n − 1) strips regardless of
locality, while ring/hierarchical shrink with the tiles' strip spans —
O(n_rows) vs O(n_rows/n · hops) per device, out to 100 simulated nodes.
A measured leg re-runs the small-n points on real simulated device
meshes (subprocess; ``run_er(mesh=...)`` with flat vs ring comms) and
reports wall time plus the executor's own byte counters, checking
match-set equality against the single-host run.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.core import compute_bdm, plan_basic, plan_block_split, plan_pair_range
from repro.er import ERConfig, run_er
from repro.er.blocking import prefix_block_ids
from repro.er.compiler import comms_volume, lower, plan_to_job
from repro.er.datasets import make_products, make_publications

from .common import print_table, save_rows

NODES = (1, 2, 5, 10, 20, 40, 100)
MEASURED_NODES = (2, 4, 8)

_MARK = "FIG13_MEASURED "

MEASURED_SCRIPT = textwrap.dedent("""
    import json, os, sys, time
    n_dev, n_corpus = int(sys.argv[1]), int(sys.argv[2])
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + str(n_dev))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from dataclasses import replace
    from repro.er import ERConfig, run_er
    from repro.er.datasets import make_products
    from repro.er.compiler.execute import stage1_stats
    from repro.sharding import make_er_mesh

    cfg = ERConfig(strategy="pair_range", r=10 * n_dev, m=2 * n_dev,
                   feature_dim=128, max_len=48)
    titles = make_products(n_corpus, seed=1).titles
    host = run_er(titles, cfg)
    mesh = make_er_mesh(n_dev)
    rows = []
    for comms in ("flat", "ring"):
        before = dict(stage1_stats["interconnect"])
        t0 = time.perf_counter()
        res = run_er(titles, replace(cfg, comms=comms), mesh=mesh)
        wall = time.perf_counter() - t0
        after = stage1_stats["interconnect"]
        rows.append({
            "policy": comms, "equal": res.matches == host.matches,
            "wall_s": round(wall, 2),
            "flat_gather_B": after["flat_bytes"] - before["flat_bytes"],
            "ring_B": after["ring_bytes"] - before["ring_bytes"],
        })
    print("FIG13_MEASURED " + json.dumps(rows))
""")


def _measure_cost_per_pair(n_sample: int = 8_000) -> float:
    ds = make_products(n_sample)
    res = run_er(ds.titles, ERConfig(strategy="pair_range", r=16, m=8))
    return float(res.reducer_seconds.sum()) / max(res.total_pairs, 1)


def _bdm_overhead(n_entities: int, n_nodes: int) -> float:
    # one counting pass over the entities, spread over nodes + fixed job cost
    return 2e-7 * n_entities / n_nodes + 1.0


def _measured_leg(quick: bool) -> list:
    rows = []
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    nodes = MEASURED_NODES[:2] if quick else MEASURED_NODES
    n_corpus = 1500 if quick else 3000
    for n_dev in nodes:
        proc = subprocess.run(
            [sys.executable, "-c", MEASURED_SCRIPT,
             str(n_dev), str(n_corpus)],
            env=env, capture_output=True, text=True, timeout=1800,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        if proc.returncode != 0:
            raise RuntimeError(f"measured leg n={n_dev} failed:\n"
                               + proc.stdout + proc.stderr)
        for line in proc.stdout.splitlines():
            if line.startswith(_MARK):
                for r in json.loads(line[len(_MARK):]):
                    assert r.pop("equal"), \
                        f"measured mesh run diverged at n={n_dev}: {r}"
                    rows.append({"dataset": "DS1-measured", "nodes": n_dev,
                                 "strategy": f"pair_range/{r['policy']}",
                                 "makespan_s": r["wall_s"],
                                 "flat_gather_B": r["flat_gather_B"],
                                 "ring_B": r["ring_B"]})
    return rows


def run(ds1_n: int = 114_000, ds2_n: int = 1_390_000, quick: bool = False):
    if quick:
        ds1_n, ds2_n = 20_000, 60_000
    cpp = _measure_cost_per_pair()
    rows = []
    for make, nn, tag in ((make_products, ds1_n, "DS1"),
                          (make_publications, ds2_n, "DS2")):
        ds = make(nn)
        bid, _ = prefix_block_ids(ds.titles, ds.prefix_len)
        n_ent = ds.n
        for n in NODES:
            m, r = 2 * n, 10 * n
            part = np.minimum(np.arange(n_ent) * m // n_ent, m - 1)
            bdm = compute_bdm(bid, part, int(bid.max()) + 1, m)
            plans = {
                "basic": plan_basic(bdm, r),
                "block_split": plan_block_split(bdm, r),
                "pair_range": plan_pair_range(bdm, r),
            }
            for strat, plan in plans.items():
                loads = plan.reducer_pairs
                # r=10n reducers over n nodes with 2 cores: each core runs
                # 5 reducers; node time = its reducers' load sum — use the
                # round-robin node assignment of compiler.device_assignment.
                node_of = np.arange(r) % (2 * n)
                core_loads = np.bincount(node_of, weights=loads,
                                         minlength=2 * n)
                makespan = core_loads.max() * cpp + _bdm_overhead(n_ent, n)
                # Exact per-device gather bytes each comms policy would
                # move for THIS strategy's tile catalog at n shards.
                vol = comms_volume(lower(plan_to_job(plan), 128, 128),
                                   n_ent, n, feature_dim=128)
                rows.append({
                    "dataset": tag, "nodes": n, "strategy": strat,
                    "max_core_load": int(core_loads.max()),
                    "makespan_s": round(float(makespan), 2),
                    "flat_gather_B": vol["flat_gather"],
                    "ring_B": vol["ring"],
                    "hier_B": vol["hier_intra"] + vol["hier_inter"],
                    "ring_hops": vol["ring_hops"],
                })
    # speedups relative to n=1
    for tag in ("DS1", "DS2"):
        for strat in ("basic", "block_split", "pair_range"):
            sel = [r for r in rows
                   if r["dataset"] == tag and r["strategy"] == strat]
            base = sel[0]["makespan_s"]
            for r_ in sel:
                r_["speedup"] = round(base / r_["makespan_s"], 2)
    rows.extend(_measured_leg(quick))
    print_table("Figs. 13/14 — node scalability (modeled + measured)", rows)
    save_rows("fig13_scaling", rows)
    return rows


if __name__ == "__main__":
    run()
