"""Quickstart: the paper's workflow in 30 lines.

    PYTHONPATH=src python examples/quickstart.py

Deduplicates a 5k-product catalog three ways (Basic / BlockSplit /
PairRange) and prints the skew story: identical matches, wildly
different load balance.
"""
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.er import ERConfig, make_products, run_er

use_compile_cache()

ds = make_products(5_000, seed=0)
print(f"dataset: {ds.n} product titles, {len(ds.true_pairs)} injected duplicates")

for strategy in ("basic", "block_split", "pair_range"):
    cfg = ERConfig(strategy=strategy, r=16, m=8)
    res = run_er(ds.titles, cfg)
    recall = len(res.matches & ds.true_pairs) / len(ds.true_pairs)
    loads = res.reducer_pairs
    print(f"{strategy:12s} pairs={res.total_pairs:>9,} "
          f"matches={len(res.matches):>5} recall={recall:.3f} "
          f"reducer imbalance (max/mean planned pairs)="
          f"{loads.max() / max(loads.mean(), 1):>6.2f} "
          f"map-output={res.map_output_size}")

print("\nthe point: one block holds ~70% of all pairs — Basic pins it to a "
      "single reducer;\nBlockSplit/PairRange split it, with identical match "
      "output.")
