"""Resident ER service quickstart: ingest a product corpus once, then
answer match micro-batches from the warm compiled-shape cache — the
serving analog of the batch ``run_er`` pipeline (paper Fig. 2), built on
the two-source R × S plans of Appendix I.

    PYTHONPATH=src python examples/match_service.py
"""
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.er import ERService, ServiceConfig, compile_counter, make_products

use_compile_cache()

CORPUS_N, BATCHES = 3_000, 8

ds = make_products(CORPUS_N, seed=0)

# Ingest once: features + block layout go resident, the BDM stays host-side.
cfg = ServiceConfig(feature_dim=128, max_len=48, r=16, m=4,
                    query_buckets=(8, 32, 128), tile_chunk=128)
svc = ERService(ds.titles, cfg)
print(f"ingested {svc.n_corpus} entities, {svc.bdm.shape[0]} blocks, "
      f"{svc.ingest_seconds*1e3:.0f} ms")

with compile_counter() as warm:
    svc.warmup()
print(f"warmup compiled everything in {warm.count} XLA compilations")

# Steady state: perturbed corpus titles (≈ near-duplicates), a null-key
# query, and a never-seen block — zero new compilations from here on.
rng = np.random.default_rng(1)
with compile_counter() as steady:
    for i in range(BATCHES):
        size = int(rng.integers(1, 100))
        batch = []
        for _ in range(size):
            t = ds.titles[int(rng.integers(0, len(ds.titles)))]
            s = list(t)
            s[int(rng.integers(3, len(s)))] = "x"
            batch.append("".join(s))
        if i == 3:
            batch[0] = ""                        # null key → match_⊥ path
        if i == 5:
            batch[0] = "@@@ brand new block"     # grows the BDM
        found = svc.match(batch)
        print(f"batch {i}: {len(batch):3d} queries → {len(found):3d} matches")
        for c, q in sorted(found)[:2]:
            print(f"    corpus[{c}] {ds.titles[c]!r}  ≈  query {batch[q]!r}")

s = svc.stats
print(f"\nserved {s['queries']} queries in {s['batches']} batches, "
      f"{s['matches']} matches, {s['planned_pairs']:,} planned cross pairs, "
      f"{s['queries']/max(s['seconds'],1e-9):,.0f} queries/s, "
      f"{steady.count} steady-state recompiles")
print("bucket hits:", s["bucket_hits"])
print("traffic skew (top-5 blocks):",
      np.sort(svc.traffic_bdm[:, 0])[::-1][:5].tolist())
