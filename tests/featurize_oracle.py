"""Per-title featurization: the straightforward form of ``er.encode``.

``encode_titles`` fills one row per title in a Python loop;
``ngram_features`` hashes every one of the ``L - n + 1`` window columns
of every row with uint64 FNV-1a, masks the windows past each title's
length, and scatters the counts with ``np.add.at``. The array passes in
``repro.er.encode`` must return exactly what this gives, bit for bit.
"""
import numpy as np

_FNV_OFFSET = np.uint64(14695981039346656037)
_FNV_PRIME = np.uint64(1099511628211)


def encode_titles(titles, max_len=64):
    """(n, max_len) uint8 char codes (0-padded) and (n,) int32 lengths."""
    n = len(titles)
    out = np.zeros((n, max_len), np.uint8)
    lens = np.zeros(n, np.int32)
    for i, t in enumerate(titles):
        raw = t.encode("utf-8", errors="replace")[:max_len]
        out[i, : len(raw)] = np.frombuffer(raw, np.uint8)
        lens[i] = len(raw)
    return out, lens


def _fnv1a_rows(mat):
    """Row-wise FNV-1a over a (rows, n) uint8 matrix -> (rows,) uint64."""
    with np.errstate(over="ignore"):
        h = np.full(mat.shape[0], _FNV_OFFSET, np.uint64)
        for c in range(mat.shape[1]):
            h = (h ^ mat[:, c].astype(np.uint64)) * _FNV_PRIME
    return h


def ngram_features(titles, dim=256, n=3, max_len=64, lengths=None,
                   dtype=np.float32):
    """Hashed char n-gram count features, L2-normalized. (num, dim)."""
    if isinstance(titles, np.ndarray):
        codes, lens = titles, np.asarray(lengths, np.int64)
    else:
        codes, lens = encode_titles(titles, max_len=max_len)
        lens = lens.astype(np.int64)
    num, L = codes.shape
    feats = np.zeros((num, dim), dtype)
    if L >= n:
        windows = np.lib.stride_tricks.sliding_window_view(codes, n, axis=1)
        ngrams = windows.reshape(num * windows.shape[1], n)
        buckets = (_fnv1a_rows(ngrams) % np.uint64(dim)).astype(np.int64)
        buckets = buckets.reshape(num, windows.shape[1])
        valid = (np.arange(windows.shape[1])[None, :] + n) <= lens[:, None]
        rows = np.repeat(np.arange(num), windows.shape[1])
        np.add.at(feats, (rows[valid.ravel()], buckets.ravel()[valid.ravel()]),
                  1.0)
    short = lens < n
    if short.any():
        h = (_fnv1a_rows(codes[short]) % np.uint64(dim)).astype(np.int64)
        feats[np.flatnonzero(short), h] += 1.0
    norms = np.linalg.norm(feats, axis=1, keepdims=True)
    return (feats / np.maximum(norms, 1e-12)).astype(dtype)
