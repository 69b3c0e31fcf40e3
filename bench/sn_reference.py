#!/usr/bin/env python3
"""The plain reference for Sorted Neighborhood cells: the band's matches.

The semantics (Kolb, Thor & Rahm, arXiv:1010.3053, with the program's
stated filter): records are sorted on their stripped, lower-cased title,
records with equal keys keeping their input order (a stable sort). Every
pair of sorted positions i < j with j − i ≤ w − 1 is compared, and it
matches under the matcher of ``bench/reference.py``: the cosine of the
hashed character-trigram count vectors at least ``threshold -
filter_margin`` (a pair within ``band`` of that cut may go either way)
and the normalized edit similarity at least ``threshold``. The
featurizing, the edit distance and that classification are
``reference``'s; nothing here comes from the program.

The band depends on the record order only through ties: a record whose
key no other record has sits at the same sorted position in every order.
So the pairs of two untied records are scored once, over the corpus as
built, with exact integer dots on the default device, one diagonal of
the band at a time; each job's order then adds the pairs that hold a
tied record, and a pair's verdict is computed once and cached.

    python3 bench/sn_reference.py --config bench/configs/ds2_sn_w1000.json \\
        --seed 7 --control

prints, for the corpus a seed builds, how far the control (the same
matcher with its stage-1 cosine from bfloat16 features) lies from the
reference: the number of pairs the cell's check would count against it.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import reference
from reference import Semantics

__all__ = ["SNReference", "semantics", "sort_order"]

_DIAGS = 64            # band diagonals per device call
_DIAGS_PAD = 1 << 12   # zero rows after the corpus: the widest window read
_KEEP = 1 << 18        # candidates returned per call before a full re-pass


def semantics(config: dict) -> Semantics:
    m = config["matcher"]
    return Semantics(threshold=float(m["threshold"]),
                     filter_margin=float(m["filter_margin"]),
                     feature_dim=int(m["feature_dim"]),
                     max_len=int(m["max_len"]))


def _key_ranks(titles: Sequence[str]) -> np.ndarray:
    """Dense rank of each record's sort key."""
    keys = [t.strip().lower() for t in titles]
    rank = {k: i for i, k in enumerate(sorted(set(keys)))}
    return np.array([rank[k] for k in keys], np.int64)


def sort_order(ranks: np.ndarray, position: np.ndarray) -> np.ndarray:
    """Record ids in sorted order: by key, ties by ``position`` (each
    record's place in the input)."""
    return np.lexsort((position, ranks))


_diag_fns: Dict[int, object] = {}


def _diag_kernel(keep: int):
    """One jitted pass over ``_DIAGS`` band diagonals: exact integer dots
    of rows i and i + d, a float32 pre-filter a little below the cut, and
    the first ``keep`` survivors' (flat index, dot)."""
    if keep not in _diag_fns:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def fn(xp, sqp, n, offsets, lo):
            rows, dim = xp.shape[0] - _DIAGS_PAD, xp.shape[1]
            x = xp[:rows]
            sq = sqp[:rows]
            i = jnp.arange(rows)

            def one(d):
                y = jax.lax.dynamic_slice(xp, (d, 0), (rows, dim))
                qy = jax.lax.dynamic_slice_in_dim(sqp, d, rows)
                g = jnp.sum(x * y, axis=1)
                ok = ((d > 0) & (i + d < n)
                      & (g >= lo * jnp.sqrt(sq * qy)))
                return g, ok

            g, ok = jax.lax.map(one, offsets)
            flat = ok.reshape(-1)
            idx = jnp.nonzero(flat, size=keep, fill_value=0)[0]
            return flat.sum(), idx, g.reshape(-1)[idx]

        _diag_fns[keep] = fn
    return _diag_fns[keep]


def _band_candidates(counts: np.ndarray, sq: np.ndarray, w: int,
                     lo: float) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(i, j, dot) over sorted rows: every band pair 0 < j − i ≤ w − 1
    whose float32 cosine is at least ``lo`` − 1e-4 (the exact cut is
    applied by the caller)."""
    import jax.numpy as jnp
    n, dim = counts.shape
    if w - 1 > _DIAGS_PAD:
        raise ValueError(f"window {w} is wider than {_DIAGS_PAD + 1}")
    rows = -(-max(n, 1) // 8) * 8
    xp = np.zeros((rows + _DIAGS_PAD, dim), np.float32)
    xp[:n] = counts
    sqp = np.zeros(rows + _DIAGS_PAD, np.float32)
    sqp[:n] = sq
    xd, qd = jnp.asarray(xp), jnp.asarray(sqp)
    lo32 = np.float32(lo - 1e-4)
    out_i, out_j, out_g = [], [], []
    for d0 in range(1, min(w, n), _DIAGS):
        offsets = np.arange(d0, d0 + _DIAGS, dtype=np.int32)
        offsets[offsets >= min(w, n)] = 0          # past the band: none
        args = (xd, qd, np.int32(n), jnp.asarray(offsets), lo32)
        cnt, idx, g = _diag_kernel(_KEEP)(*args)
        cnt = int(cnt)
        if cnt > _KEEP:
            cnt, idx, g = _diag_kernel(_DIAGS * rows)(*args)
            cnt = int(cnt)
        idx = np.asarray(idx)[:cnt].astype(np.int64)
        i = idx % rows
        out_i.append(i)
        out_j.append(i + offsets[idx // rows])
        out_g.append(np.asarray(g)[:cnt])
    if not out_i:
        z = np.zeros(0, np.int64)
        return z, z, np.zeros(0)
    return (np.concatenate(out_i), np.concatenate(out_j),
            np.concatenate(out_g))


class SNReference:
    """The SN match set of ``titles`` at window ``w``, for any record
    order of them.

    :meth:`job` takes a job's order (``perm[p]`` is the record at input
    position ``p``) and returns that job's :class:`reference.DedupReference`
    over record ids."""

    def __init__(self, titles: Sequence[str], w: int, sem: Semantics,
                 control: bool = False):
        self.w, self.sem, self.control = int(w), sem, control
        self.n = len(titles)
        self.codes, self.lens, self.counts, self.sq = reference.featurize(
            titles, sem)
        self.ranks = _key_ranks(titles)
        self.tied = np.bincount(self.ranks)[self.ranks] > 1
        reach = reference._CONTROL_REACH if control else sem.band
        self.lo = sem.cut - reach
        # verdict cache: keys a·n + b (a < b) already scored, and the
        # candidates among them (cosine >= lo) with their verdicts
        self._seen = np.zeros(0, np.int64)
        self._cand = {"key": np.zeros(0, np.int64), "cos": np.zeros(0),
                      "passed": np.zeros(0, bool), "ctl": np.zeros(0, bool)}
        self._untied = self._score_untied()

    # -- scoring ----------------------------------------------------------

    def _verdicts(self, a: np.ndarray, b: np.ndarray, cos: np.ndarray):
        """Add candidate pairs (a < b, cosine >= lo) to the cache."""
        dist = reference.edit_distance(self.codes[a], self.lens[a],
                                       self.codes[b], self.lens[b])
        passed = reference.edit_matches(dist, self.lens[a], self.lens[b],
                                        self.sem.threshold)
        ctl = np.zeros(a.size, bool)
        if self.control:
            cc = reference._bf16_cosine(self.counts, self.sq, a, b)
            ctl = (cc >= np.float32(self.sem.cut)) & passed
        c = self._cand
        c["key"] = np.r_[c["key"], a * self.n + b]
        c["cos"] = np.r_[c["cos"], cos]
        c["passed"] = np.r_[c["passed"], passed]
        c["ctl"] = np.r_[c["ctl"], ctl]

    def _score_untied(self) -> np.ndarray:
        """Cache the verdicts of every band pair of two untied records
        (the same in every order); their keys."""
        order = sort_order(self.ranks, np.arange(self.n))
        i, j, g = _band_candidates(self.counts[order], self.sq[order],
                                   self.w, self.lo)
        a, b = order[i], order[j]
        keep = ~self.tied[a] & ~self.tied[b]
        a, b, g = a[keep], b[keep], g[keep]
        a, b = np.minimum(a, b), np.maximum(a, b)
        cos = reference._cos(g, self.sq[a], self.sq[b])
        hit = cos >= self.lo
        self._verdicts(a[hit], b[hit], cos[hit])
        return self._cand["key"].copy()

    def _tie_pairs(self, pos: np.ndarray) -> np.ndarray:
        """Keys of this order's band pairs that hold a tied record."""
        t = np.flatnonzero(self.tied)
        if t.size == 0:
            return np.zeros(0, np.int64)
        order = np.empty(self.n, np.int64)
        order[pos] = np.arange(self.n)
        d = np.r_[np.arange(-(self.w - 1), 0), np.arange(1, self.w)]
        q = pos[t][:, None] + d[None, :]
        ok = (q >= 0) & (q < self.n)
        a = np.broadcast_to(t[:, None], q.shape)[ok]
        b = order[q[ok]]
        return np.unique(np.minimum(a, b) * self.n + np.maximum(a, b))

    def _score_new(self, keys: np.ndarray) -> None:
        new = keys[~np.isin(keys, self._seen)]
        if new.size:
            a, b = new // self.n, new % self.n
            dot = np.einsum("pd,pd->p", self.counts[a].astype(np.int64),
                            self.counts[b].astype(np.int64))
            cos = reference._cos(dot, self.sq[a], self.sq[b])
            hit = cos >= self.lo
            self._verdicts(a[hit], b[hit], cos[hit])
            self._seen = np.union1d(self._seen, new)

    # -- one job ----------------------------------------------------------

    def job(self, perm: Optional[np.ndarray] = None
            ) -> reference.DedupReference:
        """The reference for the records in the order ``perm`` (the
        corpus as built when None)."""
        if perm is None:
            perm = np.arange(self.n)
        place = np.empty(self.n, np.int64)
        place[np.asarray(perm, np.int64)] = np.arange(self.n)
        order = sort_order(self.ranks, place)
        pos = np.empty(self.n, np.int64)
        pos[order] = np.arange(self.n)
        ties = self._tie_pairs(pos)
        self._score_new(ties)
        c = self._cand
        mine = np.isin(c["key"], self._untied) | np.isin(c["key"], ties)
        key, cos, passed = c["key"][mine], c["cos"][mine], c["passed"][mine]
        a, b = key // self.n, key % self.n
        sure, amb = reference._classify(a, b, cos, passed, self.sem)
        we = min(self.w, self.n)
        total = (we - 1) * self.n - we * (we - 1) // 2
        ref = reference.DedupReference(
            sure=sure, cut=amb, pairs_examined=int(total),
            candidates=int((cos >= self.sem.cut - self.sem.band).sum()),
            candidates_above=int((cos > self.sem.cut + self.sem.band).sum()))
        if self.control:
            hit = c["ctl"][mine]
            ref.control = {(int(x), int(y)) for x, y in zip(a[hit], b[hit])}
        return ref


def main(argv=None) -> int:
    import argparse
    import json
    import time

    from corpus import build_corpus

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--control", action="store_true")
    args = p.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    corpus = build_corpus(config, args.seed)
    t0 = time.perf_counter()
    ref = SNReference(corpus.titles, int(config["window"]),
                      semantics(config), control=args.control)
    res = ref.job()
    out = {"seed": args.seed, "records": corpus.n,
           "band_pairs": res.pairs_examined, "sure": len(res.sure),
           "near_cut_passing": len(res.cut),
           "tied_records": int(ref.tied.sum()),
           "seconds": time.perf_counter() - t0}
    if args.control:
        missing, extra = reference.compare(set(), res, res.control)
        out["control_mismatched_pairs"] = missing + extra
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
