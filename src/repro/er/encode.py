"""Entity featurization.

Two encodings per entity title:

  * ``encode_titles`` — fixed-length uint8 char codes (+ lengths), the
    input to the exact edit-distance verifier (the paper's matcher).
  * ``ngram_features`` — L2-normalized hashed character-n-gram count
    vectors. Cosine similarity over these is a pure matmul, i.e. MXU
    work — the production filter stage in front of the verifier
    (DESIGN.md §2 "Edit distance on MXU").

Hashing is FNV-1a over the n-gram bytes — deterministic across runs and
processes (no PYTHONHASHSEED dependence), vectorized in numpy.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

__all__ = ["encode_titles", "ngram_features"]

_FNV_OFFSET = np.uint64(14695981039346656037)
_FNV_PRIME = np.uint64(1099511628211)
# (offset ^ byte) * prime for every byte value: FNV-1a's first round.
_FNV_FIRST = (_FNV_OFFSET ^ np.arange(256, dtype=np.uint64)) * _FNV_PRIME


def encode_titles(titles: Sequence[str], max_len: int = 64) -> Tuple[np.ndarray, np.ndarray]:
    """(n, max_len) uint8 char codes (0-padded) and (n,) int32 lengths.

    One bytes cast builds the codes: ``S{max_len}`` cuts each title past
    ``max_len`` bytes and zero-pads it, and the lengths come from the
    encoded titles, so embedded NUL bytes count as bytes.
    """
    raw = [t.encode("utf-8", errors="replace") for t in titles]
    n = len(raw)
    lens = np.minimum(np.fromiter(map(len, raw), np.int64, count=n), max_len)
    codes = np.array(raw, dtype=f"S{max_len}").view(np.uint8)
    return codes.reshape(n, max_len), lens.astype(np.int32)


def _fnv1a_windows(codes: np.ndarray, n: int, width: int) -> np.ndarray:
    """FNV-1a over the n-byte window starting at each column 0..width-1 of
    a (rows, >= width + n - 1) uint8 matrix -> (rows, width) uint64.

    The first round is a lookup in ``_FNV_FIRST``; uint64 arrays wrap on
    overflow without a warning.
    """
    h = _FNV_FIRST[codes[:, :width]]
    for c in range(1, n):
        h ^= codes[:, c:c + width]
        h *= _FNV_PRIME
    return h


def ngram_features(
    titles: Sequence[str] | np.ndarray,
    dim: int = 256,
    n: int = 3,
    max_len: int = 64,
    lengths: np.ndarray | None = None,
    dtype=np.float32,
) -> np.ndarray:
    """Hashed char n-gram count features, L2-normalized. (num, dim).

    Accepts raw strings or a pre-encoded (num, max_len) uint8 matrix (with
    ``lengths``). Titles shorter than ``n`` fall back to a single hash of
    the whole (padded) title so no row is all-zero.

    Each row's window buckets are sorted, and the counts are their run
    lengths, so no scatter adds to one cell twice. Only the nonzero
    counts are normalised: their squares sum to a small integer, exact
    in float32, so the norms equal the dense ``np.linalg.norm`` bit for
    bit.
    """
    if isinstance(titles, np.ndarray):
        codes, lens = titles, np.asarray(lengths, np.int64)
    else:
        codes, lens = encode_titles(titles, max_len=max_len)
        lens = lens.astype(np.int64)
    num, L = codes.shape
    feats = np.zeros((num, dim), dtype)
    width = L - n + 1
    if width > 0:
        buckets = _fnv1a_windows(codes, n, width) % np.uint64(dim)
        # Windows past a title's end take bucket `dim` and sort last; each
        # row's sorted buckets then give its counts as run lengths.
        buckets[np.arange(n, width + n) > lens[:, None]] = dim
        buckets.sort(axis=1)
        flat = buckets.reshape(-1)
        edge = np.ones(flat.size + 1, bool)     # where a run starts or ends
        np.not_equal(flat[1:], flat[:-1], out=edge[1:-1])
        edge[:-1:width] = True                  # each row starts a run
        edges = np.flatnonzero(edge)
        pos, runs = edges[:-1], np.diff(edges)
        live = flat[pos] < dim
        pos, runs = pos[live], runs[live]
        rows = pos // width
        norms = np.sqrt(np.bincount(rows, runs * runs, num).astype(dtype))
        feats.reshape(-1)[rows * dim + flat[pos].astype(np.intp)] = (
            runs.astype(dtype) / norms[rows])
    # A title shorter than n has no window: one hash of its padded row,
    # a single count, whose normalised value is 1.
    short = lens < n
    if short.any():
        h = _fnv1a_windows(codes[short], L, 1)[:, 0] % np.uint64(dim)
        feats[np.flatnonzero(short), h.astype(np.intp)] = 1.0
    return feats
