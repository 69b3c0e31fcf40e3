"""The plain reference for every cell: who matches whom, computed directly.

The semantics (the paper's matcher with the program's stated filter):
two records match when they share a blocking key (the first ``k``
characters of the stripped, lower-cased title), the cosine of their
hashed character-trigram count vectors is at least ``threshold -
filter_margin``, and their normalized edit similarity ``1 - d /
max(len_a, len_b)`` over the first ``max_len`` UTF-8 bytes is at least
``threshold``. Trigrams hash with 64-bit FNV-1a into ``feature_dim``
buckets.

Nothing here comes from the program. The trigram counts are small
integers, exact in bfloat16 and float32, so the dots below are exact
(every partial sum is an integer under 2**24) and the cosine is taken in
float64 from the exact dot and norms. The edit distance is a plain anti-diagonal dynamic
program, run with JAX on the default device only because the larger
cells hold millions of candidates; its arithmetic is integer.

A pair whose exact cosine lies within ``band`` of the cut is
*ambiguous*: a float32 program may place it on either side, so the
comparison accepts it either way and lists it (``DedupReference.cut``).

``control=True`` also computes the control: the same matcher with the
stage-1 cosine taken from bfloat16 features (float32 accumulation), the
precision step below the configuration's float32 features.
"""
from __future__ import annotations

from dataclasses import dataclass
from fractions import Fraction
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["Semantics", "featurize", "edit_distance", "edit_matches",
           "DedupReference", "dedup_reference", "cross_reference",
           "compare", "explain"]

_FNV_OFFSET = np.uint64(14695981039346656037)
_FNV_PRIME = np.uint64(1099511628211)
_CONTROL_REACH = 0.02      # bf16 moves a cosine by < 0.01 at d = 256
_EDIT_CHUNK = 1 << 16      # pairs per edit-distance call


@dataclass(frozen=True)
class Semantics:
    prefix_len: int = 3
    threshold: float = 0.8
    filter_margin: float = 0.25
    feature_dim: int = 256
    max_len: int = 64
    band: float = 1e-6

    @property
    def cut(self) -> float:
        return self.threshold - self.filter_margin

    @classmethod
    def of(cls, config: dict) -> "Semantics":
        m = config["matcher"]
        return cls(prefix_len=int(config["prefix_len"]),
                   threshold=float(m["threshold"]),
                   filter_margin=float(m["filter_margin"]),
                   feature_dim=int(m["feature_dim"]),
                   max_len=int(m["max_len"]))


def block_key(title: str, k: int) -> Optional[str]:
    key = title.strip().lower()[:k]
    return key or None


def featurize(titles: Sequence[str], sem: Semantics):
    """(codes (n, max_len) uint8, lengths (n,), counts (n, dim) float32
    holding integers, squared norms (n,) int64)."""
    n, L = len(titles), sem.max_len
    codes = np.zeros((n, L), np.uint8)
    lens = np.zeros(n, np.int64)
    for i, t in enumerate(titles):
        raw = t.encode("utf-8", errors="replace")[:L]
        codes[i, :len(raw)] = np.frombuffer(raw, np.uint8)
        lens[i] = len(raw)
    dim = sem.feature_dim
    with np.errstate(over="ignore"):
        h = np.full((n, L - 2), _FNV_OFFSET, np.uint64)
        for c in range(3):
            h = (h ^ codes[:, c:L - 2 + c].astype(np.uint64)) * _FNV_PRIME
        live = np.arange(L - 2)[None, :] + 3 <= lens[:, None]
        flat = (np.arange(n)[:, None] * dim
                + (h % np.uint64(dim)).astype(np.int64))[live]
        short = np.flatnonzero(lens < 3)
        if short.size:       # a title under three bytes hashes whole
            hs = np.full(short.size, _FNV_OFFSET, np.uint64)
            for c in range(L):
                hs = (hs ^ codes[short, c].astype(np.uint64)) * _FNV_PRIME
            flat = np.concatenate(
                [flat, short * dim + (hs % np.uint64(dim)).astype(np.int64)])
    counts = np.bincount(flat, minlength=n * dim).reshape(n, dim).astype(
        np.float32)
    sq = (counts.astype(np.int64) ** 2).sum(axis=1)
    return codes, lens, counts, sq


# ---------------------------------------------------------------------------
# Edit distance: anti-diagonal dynamic program
# ---------------------------------------------------------------------------

_edit_fn = None


def _edit_kernel():
    global _edit_fn
    if _edit_fn is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def dist(a, la, b, lb):
            p, L = a.shape
            big = jnp.int32(1 << 14)
            i = jnp.arange(L + 1, dtype=jnp.int32)[None, :]
            a = a.astype(jnp.int32)
            b = b.astype(jnp.int32)
            target = la + lb
            d0 = jnp.where(i == 0, 0, big) + jnp.zeros((p, 1), jnp.int32)
            d1 = jnp.where(i <= 1, 1, big) + jnp.zeros((p, 1), jnp.int32)
            out = jnp.where(target == 0, 0, jnp.where(target == 1, 1, big))

            # a[i-1] for every row i of a diagonal, and b[j-1] = b[k-1-i]
            # as a window that shifts by one column per diagonal (no
            # gathers): bw holds b[k-1-i] at column i for diagonal k.
            ai = jnp.concatenate([jnp.zeros((p, 1), jnp.int32), a], 1)
            b_pad = jnp.concatenate([b, jnp.zeros((p, L + 2), jnp.int32)], 1)
            bw0 = jnp.concatenate([b[:, 1:2], b[:, 0:1],
                                   jnp.zeros((p, L - 1), jnp.int32)], 1)

            def diag(k, carry):
                dm2, dm1, bw, out = carry
                j = k - i                                       # (1, L+1)
                sub = (ai != bw).astype(jnp.int32)
                up = jnp.concatenate([jnp.full((p, 1), big), dm1[:, :-1]], 1)
                diag2 = jnp.concatenate([jnp.full((p, 1), big),
                                         dm2[:, :-1]], 1)
                cell = jnp.minimum(jnp.minimum(up + 1, dm1 + 1), diag2 + sub)
                cell = jnp.where(i == 0, k, jnp.where(j == 0, k, cell))
                cell = jnp.where((j < 0) | (j > L), big, cell)
                hit = jnp.sum(jnp.where(i == la[:, None], cell, 0), axis=1)
                out = jnp.where(target == k, hit, out)
                bw = jnp.concatenate(
                    [jax.lax.dynamic_slice_in_dim(b_pad, k, 1, axis=1),
                     bw[:, :-1]], 1)
                return dm1, cell, bw, out

            _, _, _, out = jax.lax.fori_loop(2, 2 * L + 1, diag,
                                             (d0, d1, bw0, out))
            return out

        _edit_fn = dist
    return _edit_fn


def edit_distance(codes_a, lens_a, codes_b, lens_b) -> np.ndarray:
    """Levenshtein distance of each row pair, on the default device. The
    table spans only the longest title present (a power of two, at
    least 8 bytes): the distance of two prefixes never reads past them."""
    import jax.numpy as jnp
    fn = _edit_kernel()
    n = codes_a.shape[0]
    out = np.zeros(n, np.int64)
    longest = int(max(lens_a.max(initial=0), lens_b.max(initial=0), 1))
    width = min(codes_a.shape[1], max(8, 1 << (longest - 1).bit_length()))
    for lo in range(0, n, _EDIT_CHUNK):
        hi = min(lo + _EDIT_CHUNK, n)
        size = hi - lo
        pad = _EDIT_CHUNK if n > _EDIT_CHUNK else max(
            1 << max(size - 1, 1).bit_length(), 8)
        a = np.zeros((pad, width), np.uint8)
        b = np.zeros_like(a)
        la = np.zeros(pad, np.int32)
        lb = np.zeros(pad, np.int32)
        a[:size], b[:size] = codes_a[lo:hi, :width], codes_b[lo:hi, :width]
        la[:size], lb[:size] = lens_a[lo:hi], lens_b[lo:hi]
        d = fn(jnp.asarray(a), jnp.asarray(la), jnp.asarray(b),
               jnp.asarray(lb))
        out[lo:hi] = np.asarray(d)[:size]
    return out


def edit_matches(dist: np.ndarray, la: np.ndarray, lb: np.ndarray,
                 threshold: float) -> np.ndarray:
    """``1 - d / max(la, lb, 1) >= threshold`` in exact integer form,
    with the threshold read as the decimal the configuration states."""
    slack = 1 - Fraction(str(threshold))
    return (slack.denominator * dist
            <= slack.numerator * np.maximum(np.maximum(la, lb), 1))


# ---------------------------------------------------------------------------
# Stage 1: exact cosine over same-key pairs
# ---------------------------------------------------------------------------

def _block_ids(titles: Sequence[str], k: int) -> np.ndarray:
    """Dense block id per record, -1 for a record with no key."""
    ids = np.empty(len(titles), np.int64)
    seen: Dict[str, int] = {}
    for i, t in enumerate(titles):
        key = block_key(t, k)
        ids[i] = -1 if key is None else seen.setdefault(key, len(seen))
    return ids


def _cos(dot: np.ndarray, sq_a: np.ndarray, sq_b: np.ndarray) -> np.ndarray:
    return dot.astype(np.float64) / np.sqrt(
        np.maximum(sq_a * sq_b, 1).astype(np.float64))


_tile_fns: Dict[int, object] = {}


def _tile_kernel(keep: int):
    """One jitted pass over ``_TILES`` tiles of the sorted block
    diagonal: exact integer dots, the same-block and a < b masks, a
    float32 pre-filter a little below the cut, and the first ``keep``
    survivors' (flat index, dot)."""
    if keep not in _tile_fns:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def fn(x, blk, sq, ti, tj, lo):
            t = _TILE

            def one(i, j):
                a = jax.lax.dynamic_slice_in_dim(x, i * t, t)
                b = jax.lax.dynamic_slice_in_dim(x, j * t, t)
                g = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                ba = jax.lax.dynamic_slice_in_dim(blk, i * t, t)
                bb = jax.lax.dynamic_slice_in_dim(blk, j * t, t)
                qa = jax.lax.dynamic_slice_in_dim(sq, i * t, t)
                qb = jax.lax.dynamic_slice_in_dim(sq, j * t, t)
                ra = i * t + jnp.arange(t)
                rb = j * t + jnp.arange(t)
                ok = ((ba[:, None] == bb[None, :]) & (ba[:, None] >= 0)
                      & (ra[:, None] < rb[None, :])
                      & (g >= lo * jnp.sqrt(qa[:, None] * qb[None, :])))
                return g, ok

            g, ok = jax.vmap(one)(ti, tj)
            flat = ok.reshape(-1)
            idx = jnp.nonzero(flat, size=keep, fill_value=0)[0]
            return flat.sum(), idx, g.reshape(-1)[idx]

        _tile_fns[keep] = fn
    return _tile_fns[keep]


_TILE = 128            # rows per side of a reference tile
_TILES = 256           # tiles per device call
_KEEP = 1 << 18        # survivors returned per call before a full re-pass


def _self_candidates(counts, sq, block, lo: float):
    """(rows_a, rows_b, cosine) of every same-block pair a < b whose
    exact cosine is at least ``lo``, and the number of same-block
    pairs. Records are sorted by block and the block diagonal is covered
    by 128 x 128 tiles, scored on the default device."""
    import jax.numpy as jnp
    n = counts.shape[0]
    order = np.argsort(block, kind="stable")
    sizes = np.bincount(block[block >= 0])
    total = int((sizes * (sizes - 1) // 2).sum())
    n_pad = -(-max(n, 1) // _TILE) * _TILE
    x = np.zeros((n_pad, counts.shape[1]), np.float32)
    x[:n] = counts[order]
    blk = np.full(n_pad, -1, np.int32)
    blk[:n] = block[order]
    sq_s = np.zeros(n_pad, np.float32)
    sq_s[:n] = sq[order]
    # the last sorted row of each block: a tile row reaches as far right
    # as the end of the block that its last row belongs to
    end = np.zeros(n_pad, np.int64)
    keyed = blk[:n] >= 0
    starts = np.flatnonzero(np.r_[True, blk[1:n] != blk[:n - 1]]) if n else []
    bounds = np.r_[starts, n]
    for s0, s1 in zip(bounds[:-1], bounds[1:]):
        end[s0:s1] = s1
    ti, tj = [], []
    for r in range(n_pad // _TILE):
        last = min((r + 1) * _TILE, n) - 1
        if last < r * _TILE or not keyed[r * _TILE:last + 1].any():
            continue
        reach = -(-int(end[last]) // _TILE)
        ti.extend([r] * (reach - r))
        tj.extend(range(r, reach))
    ti = np.asarray(ti, np.int32)
    tj = np.asarray(tj, np.int32)
    pad = (-ti.size) % _TILES
    ti = np.r_[ti, np.zeros(pad, np.int32)]   # tile (0, 0) again: its
    tj = np.r_[tj, np.zeros(pad, np.int32)]   # pairs are dropped below
    real = np.r_[np.ones(ti.size - pad, bool), np.zeros(pad, bool)]
    xd = jnp.asarray(x, jnp.bfloat16)         # integer counts: exact
    bd, qd = jnp.asarray(blk), jnp.asarray(sq_s)
    lo32 = np.float32(lo - 1e-4)
    out_a, out_b, out_g = [], [], []
    area = _TILE * _TILE
    for c0 in range(0, ti.size, _TILES):
        args = (xd, bd, qd, jnp.asarray(ti[c0:c0 + _TILES]),
                jnp.asarray(tj[c0:c0 + _TILES]), lo32)
        cnt, idx, g = _tile_kernel(_KEEP)(*args)
        cnt = int(cnt)
        if cnt > _KEEP:
            cnt, idx, g = _tile_kernel(_TILES * area)(*args)
            cnt = int(cnt)
        idx = np.asarray(idx)[:cnt].astype(np.int64)
        g = np.asarray(g)[:cnt]
        tile = c0 + idx // area
        live = real[tile]
        tile, idx, g = tile[live], idx[live], g[live]
        out_a.append(ti[tile].astype(np.int64) * _TILE + (idx % area) // _TILE)
        out_b.append(tj[tile].astype(np.int64) * _TILE + idx % _TILE)
        out_g.append(g)
    if not out_a:
        z = np.zeros(0, np.int64)
        return z, z, np.zeros(0), total
    sa, sb, g = (np.concatenate(out_a), np.concatenate(out_b),
                 np.concatenate(out_g))
    ra, rb = order[sa], order[sb]
    c = _cos(g, sq[ra], sq[rb])
    keep = c >= lo
    ra, rb = ra[keep], rb[keep]
    return (np.minimum(ra, rb), np.maximum(ra, rb), c[keep], total)


def _bf16_cosine(counts, sq, ra, rb) -> np.ndarray:
    """The control's stage-1 score: features normalized in float32,
    rounded to bfloat16, dotted with float32 accumulation on the default
    device."""
    import jax.numpy as jnp
    out = np.zeros(ra.size, np.float32)
    inv = (1.0 / np.sqrt(np.maximum(sq, 1))).astype(np.float32)
    for lo in range(0, ra.size, _EDIT_CHUNK):
        a = (counts[ra[lo:lo + _EDIT_CHUNK]]
             * inv[ra[lo:lo + _EDIT_CHUNK], None])
        b = (counts[rb[lo:lo + _EDIT_CHUNK]]
             * inv[rb[lo:lo + _EDIT_CHUNK], None])
        out[lo:lo + a.shape[0]] = np.asarray(jnp.einsum(
            "pd,pd->p", jnp.asarray(a, jnp.bfloat16),
            jnp.asarray(b, jnp.bfloat16),
            preferred_element_type=jnp.float32))
    return out


# ---------------------------------------------------------------------------
# Match sets
# ---------------------------------------------------------------------------

@dataclass
class DedupReference:
    """Pairs are (a, b) with a < b, as int64 arrays keyed in ``sure``,
    ``cut`` (ambiguous: either answer is right) and ``control`` (the
    control's answer, when asked for)."""
    sure: set
    cut: set
    pairs_examined: int
    candidates: int            # exact cosine >= cut - band
    candidates_above: int      # exact cosine > cut + band
    control: Optional[set] = None


def _classify(ra, rb, c, passed, sem: Semantics):
    cut = sem.cut
    sure = (c > cut + sem.band) & passed
    amb = (np.abs(c - cut) <= sem.band) & passed
    return ({(int(a), int(b)) for a, b in zip(ra[sure], rb[sure])},
            {(int(a), int(b)) for a, b in zip(ra[amb], rb[amb])})


def _require_keys(keyed) -> None:
    """The reference covers same-key pairs only; the program also
    matches records without a key against everyone."""
    if not np.all(keyed):
        raise ValueError("a record has no blocking key; the reference "
                         "does not cover the program's match for it")


def dedup_reference(titles: Sequence[str], sem: Semantics,
                    control: bool = False) -> DedupReference:
    """Every matching pair of a self-join over ``titles``."""
    block = _block_ids(titles, sem.prefix_len)
    _require_keys(block >= 0)
    codes, lens, counts, sq = featurize(titles, sem)
    reach = _CONTROL_REACH if control else sem.band
    ra, rb, c, total = _self_candidates(counts, sq, block, sem.cut - reach)
    dist = edit_distance(codes[ra], lens[ra], codes[rb], lens[rb])
    passed = edit_matches(dist, lens[ra], lens[rb], sem.threshold)
    sure, amb = _classify(ra, rb, c, passed, sem)
    ref = DedupReference(sure=sure, cut=amb, pairs_examined=int(total),
                         candidates=int((c >= sem.cut - sem.band).sum()),
                         candidates_above=int((c > sem.cut + sem.band).sum()))
    if control:
        cc = _bf16_cosine(counts, sq, ra, rb)
        hit = (cc >= np.float32(sem.cut)) & passed
        ref.control = {(int(a), int(b)) for a, b in zip(ra[hit], rb[hit])}
    return ref


def cross_reference(corpus: Sequence[str], queries: Sequence[str],
                    sem: Semantics, control: bool = False) -> DedupReference:
    """Every matching (corpus_index, query_index) pair."""
    _require_keys([block_key(t, sem.prefix_len) is not None
                   for t in list(corpus) + list(queries)])
    codes_c, lens_c, counts_c, sq_c = featurize(corpus, sem)
    codes_q, lens_q, counts_q, sq_q = featurize(queries, sem)
    by_key: Dict[str, List[int]] = {}
    for i, t in enumerate(corpus):
        key = block_key(t, sem.prefix_len)
        if key is not None:
            by_key.setdefault(key, []).append(i)
    q_by_key: Dict[str, List[int]] = {}
    for j, t in enumerate(queries):
        key = block_key(t, sem.prefix_len)
        if key is not None and key in by_key:
            q_by_key.setdefault(key, []).append(j)
    reach = _CONTROL_REACH if control else sem.band
    lo = sem.cut - reach
    out_a, out_b, out_c = [], [], []
    total = 0
    for key, qs in q_by_key.items():
        cs = np.asarray(by_key[key], np.int64)
        qs = np.asarray(qs, np.int64)
        total += cs.size * qs.size
        g = counts_c[cs] @ counts_q[qs].T                       # (C, Q)
        ci, qj = (ix.ravel() for ix in np.indices(g.shape))
        c = _cos(g[ci, qj], sq_c[cs[ci]], sq_q[qs[qj]])
        keep = c >= lo
        out_a.append(cs[ci[keep]])
        out_b.append(qs[qj[keep]])
        out_c.append(c[keep])
    ra = np.concatenate(out_a) if out_a else np.zeros(0, np.int64)
    rb = np.concatenate(out_b) if out_b else np.zeros(0, np.int64)
    c = np.concatenate(out_c) if out_c else np.zeros(0)
    dist = edit_distance(codes_c[ra], lens_c[ra], codes_q[rb], lens_q[rb])
    passed = edit_matches(dist, lens_c[ra], lens_q[rb], sem.threshold)
    sure, amb = _classify(ra, rb, c, passed, sem)
    ref = DedupReference(sure=sure, cut=amb, pairs_examined=int(total),
                         candidates=int((c >= sem.cut - sem.band).sum()),
                         candidates_above=int((c > sem.cut + sem.band).sum()))
    if control:
        both = np.concatenate([counts_c, counts_q])
        sq = np.concatenate([sq_c, sq_q])
        cc = _bf16_cosine(both, sq, ra, rb + len(corpus))
        hit = (cc >= np.float32(sem.cut)) & passed
        ref.control = {(int(a), int(b)) for a, b in zip(ra[hit], rb[hit])}
    return ref


def explain(pairs, titles_a: Sequence[str], titles_b: Sequence[str],
            sem: Semantics) -> List[Tuple[int, int, float, int, int, int]]:
    """(a, b, exact cosine, edit distance, len_a, len_b) of each pair,
    for reporting pairs on which a run and the reference disagree."""
    pairs = sorted(pairs)
    if not pairs:
        return []
    ia = np.array([a for a, _ in pairs])
    ib = np.array([b for _, b in pairs])
    codes_a, lens_a, counts_a, sq_a = featurize([titles_a[i] for i in ia], sem)
    codes_b, lens_b, counts_b, sq_b = featurize([titles_b[i] for i in ib], sem)
    dot = (counts_a.astype(np.int64) * counts_b.astype(np.int64)).sum(1)
    cos = _cos(dot, sq_a, sq_b)
    dist = edit_distance(codes_a, lens_a, codes_b, lens_b)
    return [(int(a), int(b), float(c), int(d), int(x), int(y))
            for a, b, c, d, x, y in zip(ia, ib, cos, dist, lens_a, lens_b)]


def compare(got: set, ref: DedupReference, answer: Optional[set] = None
            ) -> Tuple[int, int]:
    """(missing, extra): sure pairs absent from ``got``, and pairs of
    ``got`` that are neither sure nor ambiguous. ``answer`` replaces
    ``got`` (the control's own answer, say)."""
    got = got if answer is None else answer
    missing = len(ref.sure - got)
    extra = len(got - ref.sure - ref.cut)
    return missing, extra
