"""Two-source entity resolution (paper Appendix I): match a 'store A'
catalog against a 'store B' catalog — only cross-source pairs compared,
with PairRange balancing over the rectangular |Φ_R|×|Φ_S| enumeration.

    PYTHONPATH=src python examples/dedup_two_sources.py
"""
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import compute_bdm
from repro.core.two_source import (TwoSourceBDM, plan_block_split_2src,
                                   plan_pair_range_2src, pairs_of_range_2src)
from repro.er import make_products
from repro.er.blocking import prefix_block_ids
from repro.er.encode import encode_titles, ngram_features
from repro.er.similarity import edit_similarity

use_compile_cache()

R_SIZE, S_SIZE, R_TASKS = 3_000, 2_000, 12

# two overlapping catalogs: B perturbs a slice of A's titles
a = make_products(R_SIZE, seed=0)
b = make_products(S_SIZE, seed=0)      # same generator seed → overlaps
r_titles, s_titles = a.titles, b.titles

# shared dense block space over both sources (3-char prefix)
all_ids, names = prefix_block_ids(r_titles + s_titles, a.prefix_len)
rid, sid = all_ids[:len(r_titles)], all_ids[len(r_titles):]
nb = int(all_ids.max()) + 1
bdm2 = TwoSourceBDM(
    bdm_r=compute_bdm(rid, np.zeros_like(rid), nb, 1),
    bdm_s=compute_bdm(sid, np.zeros_like(sid), nb, 1))

plan = plan_pair_range_2src(bdm2, R_TASKS)
print(f"R={len(r_titles)} S={len(s_titles)} blocks={nb} "
      f"cross pairs={plan.total_pairs:,} "
      f"pairs/reducer={plan.reducer_pairs.tolist()[:6]}…")

# order each source's entities into the blocked layout
r_order = np.argsort(rid, kind="stable")
s_order = np.argsort(sid, kind="stable")
rc, rl = encode_titles([r_titles[i] for i in r_order])
sc, sl = encode_titles([s_titles[i] for i in s_order])
rf = ngram_features(rc, lengths=rl)
sf = ngram_features(sc, lengths=sl)

matches = []
for k in range(R_TASKS):
    blk, x, y, rr, ss = pairs_of_range_2src(plan, k)
    if rr.size == 0:
        continue
    cos = np.einsum("pd,pd->p", rf[rr], sf[ss])
    cand = np.flatnonzero(cos >= 0.55)
    if cand.size == 0:
        continue
    sim = np.asarray(edit_similarity(rc[rr[cand]], rl[rr[cand]],
                                     sc[ss[cand]], sl[ss[cand]]))
    hit = cand[sim >= 0.8]
    matches.extend((int(r_order[rr[i]]), int(s_order[ss[i]])) for i in hit)

print(f"cross-source matches: {len(matches)}; sample:")
for ri, si in matches[:5]:
    print(f"  A[{ri}] {r_titles[ri]!r}  ≈  B[{si}] {s_titles[si]!r}")
