"""Chaos drill quickstart (DESIGN.md §Fault tolerance): replay a seeded
failure script — kills, stragglers, transient errors, corrupted shards —
against a supervised 8-device run and watch tile-granular recovery
return the exact quiet match set; then point the same chaos at a
resident ``ERService`` and watch the circuit breaker evict the dead
device and re-admit it after its revive.

    PYTHONPATH=src python examples/chaos_drill.py
"""
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import compute_bdm, plan_block_split
from repro.er import ERService, ServiceConfig, make_products
from repro.er.blocking import exponential_block_ids
from repro.er.compiler import (FaultEvent, FaultInjector, FaultScript,
                               execute, execute_supervised, lower,
                               plan_to_job)

use_compile_cache()

N_DEV, THRESH = 8, 0.4

# ---- the paper's Fig. 9 robustness workload at s = 1.0 -------------------
rng = np.random.default_rng(9)
n = 2_000
bid = exponential_block_ids(n, b=100, s=1.0, rng=rng)
bdm = compute_bdm(bid, np.zeros(n, np.int64), int(bid.max()) + 1, 1)
catalog = lower(plan_to_job(plan_block_split(bdm, 32)), 64, 64)
feats = rng.normal(size=(n, 64)).astype(np.float32)
feats /= np.linalg.norm(feats, axis=1, keepdims=True)

quiet = set(zip(*map(np.ndarray.tolist,
                     execute(catalog, feats, threshold=THRESH))))
print(f"quiet run: {catalog.num_tiles} tiles, {len(quiet)} survivors")

# ---- executor drill: a seeded random script, replayed --------------------
script = FaultScript.random(seed=7, n_dev=N_DEV, n_events=6,
                            max_step=24, straggle_delay=1e6,
                            allow_revive=True)
for e in script.events:
    print(f"  step {e.step:2d}: {e.kind:9s} device {e.device}"
          + (f" (+{e.delay:g}s)" if e.delay else ""))
ra, rb, rep = execute_supervised(
    catalog, feats, threshold=THRESH, n_dev=N_DEV, shard_deadline=120.0,
    max_retries=8, backoff=0.0, injector=FaultInjector(script, seed=7))
assert set(zip(ra.tolist(), rb.tolist())) == quiet     # exact recovery
assert rep.coverage == 1.0 and rep.retries <= 8
failed = [r for r in rep.records if r.status != "ok"]
print(f"recovered in {rep.rounds} rounds: {len(failed)} failed shards "
      f"({', '.join(sorted({r.status for r in failed}) or ['none'])}), "
      f"{rep.recovered_tiles} tiles re-executed, coverage {rep.coverage}")
print(f"final healthy mask: {rep.healthy.astype(int).tolist()}")

# ---- service drill: circuit breaker evicts, probe re-admits --------------
ds = make_products(400, seed=3)
svc = ERService(ds.titles[:320], ServiceConfig(
    feature_dim=128, max_len=48, r=8, m=4, query_buckets=(16,),
    tile_chunk=64, exec_devices=N_DEV, backoff_s=0.0,
    breaker_threshold=1, breaker_cooldown_s=0.0))
svc.set_fault_injector(FaultInjector(FaultScript(events=(
    FaultEvent("kill", 2, 0), FaultEvent("corrupt", 4, 4),
    FaultEvent("revive", 2, 25)), n_dev=N_DEV)))
for i in range(6):
    batch = ds.titles[320 + i * 13:320 + (i + 1) * 13]
    resp = svc.match(batch)
    print(f"batch {i}: {len(resp)} matches, attempts {resp.attempts}, "
          f"coverage {resp.coverage}, evicted {sorted(svc._breaker_open)}")
s = svc.stats
assert s["degraded"] == 0
print(f"\nbreaker: {s['breaker_evictions']} evictions, "
      f"{s['breaker_readmissions']} readmissions; "
      f"{s['retries']} request retries, "
      f"{s['recovered_tiles']} tiles recovered — every response exact")
