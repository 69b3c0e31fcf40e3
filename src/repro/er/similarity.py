"""Pair similarity: the reduce-phase matcher (paper §VI: edit distance on
titles, match iff similarity ≥ 0.8).

Production path is two-stage (DESIGN.md §2):
  1. cosine over hashed n-gram features — a matmul (MXU / Pallas kernel);
  2. exact normalized edit distance on the survivors — the paper-faithful
     verifier, vectorized over pairs with an anti-diagonal-free DP: each
     DP row update is

        c[j]       = min(prev[j] + 1, prev[j-1] + subst_cost[j])
        new[j]     = min(c[j], min_{k<j}(c[k] + (j - k)))
                   = min(c[j], cummin(c - iota) + iota)

     i.e. the sequential insert chain becomes a parallel cumulative min
     (``lax.associative_scan``), so one title of length L costs L scans of
     O(L) vector work, batched over all pairs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.pair_sim import SCORE_PRECISION

__all__ = ["cosine_scores", "edit_distance", "edit_similarity",
           "two_stage_match", "edit_distance_np"]


def cosine_scores(a, b):
    """(P, d) × (P, d) row-paired cosine scores (features pre-normalized)."""
    return jnp.einsum("pd,pd->p", a, b, precision=SCORE_PRECISION,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=())
def edit_distance(a_codes, a_len, b_codes, b_len):
    """Levenshtein distance for each row pair.

    a_codes, b_codes: (P, L) uint8 (0-padded); a_len, b_len: (P,) int32.
    Returns (P,) int32. Padding is excluded by clamping the DP to the true
    lengths at the end (cells beyond a row's length never influence the
    returned cell because we read dp[b_len] after a_len row steps — we
    therefore run all L row steps but freeze rows past a_len).
    """
    P, L = a_codes.shape
    iota = jnp.arange(L + 1, dtype=jnp.int32)
    row0 = jnp.broadcast_to(iota, (P, L + 1))

    def step(prev, i):
        ai = a_codes[:, i][:, None]                       # (P, 1)
        subst = (ai != b_codes).astype(jnp.int32)         # (P, L)
        c_head = prev[:, :1] + 1
        c_tail = jnp.minimum(prev[:, 1:] + 1, prev[:, :-1] + subst)
        c = jnp.concatenate([c_head, c_tail], axis=1)     # (P, L+1)
        pm = jax.lax.associative_scan(jnp.minimum, c - iota, axis=1)
        new = jnp.minimum(c, pm + iota)
        # Freeze rows past this pair's a-length (i >= a_len): keep prev.
        keep = (i < a_len)[:, None]
        return jnp.where(keep, new, prev), None

    dp, _ = jax.lax.scan(step, row0, jnp.arange(L, dtype=jnp.int32))
    return jnp.take_along_axis(dp, b_len[:, None].astype(jnp.int32), axis=1)[:, 0]


@functools.lru_cache(maxsize=None)
def _similarity_table(L: int) -> np.ndarray:
    """(L + 1, L + 1) float32 whose entry [d, m] is 1 − d / max(m, 1),
    divided on the host in IEEE float32."""
    d = np.arange(L + 1, dtype=np.float32)[:, None]
    mx = np.maximum(np.arange(L + 1), 1).astype(np.float32)
    return 1.0 - d / mx


@functools.partial(jax.jit, static_argnums=3)
def _table_similarity(d, a_len, b_len, L: int):
    """1 − d / max(a_len, b_len) from ``_similarity_table(L)``, in one
    dispatch: an eager gather costs several per ``verify_pairs`` chunk."""
    return jnp.asarray(_similarity_table(L))[d, jnp.maximum(a_len, b_len)]


def edit_similarity(a_codes, a_len, b_codes, b_len):
    """Normalized similarity 1 − dist / max(len_a, len_b) ∈ [0, 1].

    The quotient is read from ``_similarity_table``, so ``>= threshold``
    gives the same verdict on every backend. A TPU's float32 division is
    not correctly rounded: there, a pair exactly at the threshold (3
    edits in 15 bytes at 0.8) came out just under it.
    """
    d = edit_distance(a_codes, a_len, b_codes, b_len)
    return _table_similarity(d, a_len, b_len, a_codes.shape[1])


def edit_distance_np(a: str, b: str) -> int:
    """Plain O(len_a · len_b) reference used by tests."""
    la, lb = len(a), len(b)
    dp = list(range(lb + 1))
    for i in range(1, la + 1):
        prev = dp[0]
        dp[0] = i
        for j in range(1, lb + 1):
            cur = dp[j]
            dp[j] = min(dp[j] + 1, dp[j - 1] + 1, prev + (a[i - 1] != b[j - 1]))
            prev = cur
    return dp[lb]


def two_stage_match(feats_a, feats_b, codes_a, len_a, codes_b, len_b,
                    threshold: float = 0.8, filter_margin: float = 0.25):
    """Filter-and-verify for row-paired candidates.

    Stage 1 keeps pairs with cosine ≥ threshold − margin (cheap, MXU);
    stage 2 verifies with exact edit similarity ≥ threshold. Cheap pairs
    that fail the filter skip the verifier *mathematically* (their stage-2
    result is masked), though under jit both branches are computed — the
    skipping materializes as tile-level sparsity in the Pallas/bucketed
    executor, not here.

    Returns (match_mask bool (P,), scores float32 (P,)).
    """
    cos = cosine_scores(feats_a, feats_b)
    candidate = cos >= (threshold - filter_margin)
    sim = edit_similarity(codes_a, len_a, codes_b, len_b)
    match = candidate & (sim >= threshold)
    return match, jnp.where(match, sim, 0.0)
