"""2-D mesh scale-out bench: comms-policy parity + interconnect bytes.

Runs the full ``run_er`` pipeline on simulated device meshes (each leg
is a subprocess, so the device count is pinned before jax initializes)
and compares the three stage-1 gather policies on the ``data`` axis —
flat all-gather, ring strip pipeline, hierarchical group exchange —
plus the data×model 2-D mesh (feature columns sharded, partial tile
scores psum-combined) and the multi-hop RepSN halo executor at a
window wider than a shard.

Asserted invariants (the mesh scale-out contract, DESIGN.md §Mesh
scale-out):

  * every comms policy — and the 2-D data×model mesh — produces
    EXACTLY the single-host match set;
  * at 16 simulated devices the locality-placed ring policy receives
    >= 2x fewer gather bytes per device than the flat all-gather
    (blocked workloads bound the strip span, flat always ships
    (n_dev − 1) strips);
  * the multi-hop halo exchange (w − 1 > n / n_dev) matches the
    single-host SN pipeline and its per-hop byte schedule sums to
    exactly (w − 1) feature rows per device.

Byte counts are the executor's own exact per-device accounting
(``stage1_stats["interconnect"]``, populated per kernel launch), not a
model. Results land in ``benchmarks/out/mesh_bench.json``.

    PYTHONPATH=src python -m benchmarks.mesh_bench [--smoke]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from .common import print_table, save_rows

_MARK = "MESH_BENCH_JSON "

SCRIPT = textwrap.dedent("""
    import json, os, sys, time
    n_data, n_model, n_corpus = map(int, sys.argv[1:4])
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + str(n_data * n_model))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from dataclasses import replace
    from repro.er import ERConfig, run_er
    from repro.er.datasets import make_products
    from repro.er.distributed import sn_replication_volume
    from repro.er.compiler.execute import stage1_stats
    from repro.sharding import make_er_mesh

    FLOWS = ("flat_bytes", "ring_bytes", "hier_intra_bytes",
             "hier_inter_bytes", "halo_bytes", "psum_bytes")
    def snap():
        return {k: stage1_stats["interconnect"][k] for k in FLOWS}

    cfg = ERConfig(strategy="pair_range", r=32, m=8,
                   feature_dim=128, max_len=48)
    ds = make_products(n_corpus, seed=3)
    titles = ds.titles
    mesh = make_er_mesh(n_data, n_model)
    rows = []

    host = run_er(titles, cfg)
    for comms in ("flat", "ring", "hierarchical"):
        before = snap()
        t0 = time.perf_counter()
        res = run_er(titles, replace(cfg, comms=comms), mesh=mesh)
        wall = time.perf_counter() - t0
        d = {k: stage1_stats["interconnect"][k] - before[k] for k in FLOWS}
        gather = (d["flat_bytes"] + d["ring_bytes"]
                  + d["hier_intra_bytes"] + d["hier_inter_bytes"])
        rows.append({
            "leg": "comms", "policy": comms,
            "n_data": n_data, "n_model": n_model,
            "matches": len(res.matches),
            "equal": res.matches == host.matches,
            "gather_bytes_per_dev": gather,
            "psum_bytes_per_dev": d["psum_bytes"],
            "fallback": res.extra.get("comms_fallback"),
            "wall_s": round(wall, 2),
        })

    # ---- multi-hop RepSN halo: w − 1 > n / n_data ----
    n_sn = len(titles) - (len(titles) % n_data)
    sn_titles = titles[:n_sn]
    n_loc = n_sn // n_data
    W = n_loc + max(n_loc // 4, 2)        # 2 chained hops
    sn_cfg = replace(cfg, strategy="sorted_neighborhood", window=W,
                     r=n_data)
    sn_host = run_er(sn_titles, sn_cfg)
    before = snap()
    got = run_er(sn_titles, sn_cfg, mesh=mesh).matches
    moved = {k: stage1_stats["interconnect"][k] - before[k] for k in FLOWS}
    per_hop = sn_replication_volume(n_sn, W, n_data, cfg.feature_dim,
                                    per_hop=True)
    rows.append({
        "leg": "halo", "policy": "multi-hop",
        "n_data": n_data, "n_model": n_model,
        "matches": len(got), "equal": got == sn_host.matches,
        "gather_bytes_per_dev": moved["halo_bytes"],
        "psum_bytes_per_dev": moved["psum_bytes"],
        "hops": len(per_hop),
        "hop_bytes_ok": sum(per_hop) == (W - 1) * cfg.feature_dim * 4,
        "wall_s": None,
    })
    print("MESH_BENCH_JSON " + json.dumps(rows))
""")


def _leg(n_data: int, n_model: int, n_corpus: int) -> list:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT,
         str(n_data), str(n_model), str(n_corpus)],
        env=env, capture_output=True, text=True, timeout=1800,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if proc.returncode != 0:
        raise RuntimeError(f"mesh leg ({n_data}x{n_model}) failed:\n"
                           + proc.stdout + proc.stderr)
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            return json.loads(line[len(_MARK):])
    raise RuntimeError("mesh leg produced no result line:\n" + proc.stdout)


def run(quick: bool = False):
    legs = ([(16, 1, 2000), (4, 2, 640)] if quick
            else [(8, 1, 4000), (16, 1, 4000), (4, 2, 2000)])
    rows = []
    for n_data, n_model, n_corpus in legs:
        rows.extend(_leg(n_data, n_model, n_corpus))

    # ---- contract assertions ----
    for r in rows:
        assert r["equal"], f"match-set mismatch: {r}"
        assert not r.get("fallback"), f"plan degraded to flat: {r}"
    by = {(r["n_data"], r["n_model"], r["policy"]): r
          for r in rows if r["leg"] == "comms"}
    n16_flat = by[(16, 1, "flat")]["gather_bytes_per_dev"]
    n16_ring = by[(16, 1, "ring")]["gather_bytes_per_dev"]
    assert n16_flat >= 2 * max(n16_ring, 1), \
        f"ring gather {n16_ring} not >= 2x below flat {n16_flat} at 16 dev"
    for r in rows:
        if r["leg"] == "halo":
            assert r["hops"] >= 2 and r["hop_bytes_ok"], r
    for r in rows:
        if r["leg"] == "comms" and r["policy"] != "flat":
            flat = by[(r["n_data"], r["n_model"], "flat")]
            r["reduction_x"] = round(
                flat["gather_bytes_per_dev"]
                / max(r["gather_bytes_per_dev"], 1), 1)

    print_table("Mesh scale-out — gather policy parity + exact "
                "interconnect bytes/device", rows)
    save_rows("mesh_bench", rows)
    red = by[(16, 1, "ring")].get("reduction_x")
    print(f"\nOK: exact match-set equality on every leg; ring cuts gather "
          f"bytes/device {red}x vs flat at 16 devices; multi-hop halo "
          f"exact past the single-shard window")
    return rows


if __name__ == "__main__":
    run(quick="--smoke" in sys.argv)
