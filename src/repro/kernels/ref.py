"""Pure-jnp oracles for every Pallas kernel (the allclose targets of the
per-kernel sweep tests). ``pair_scores_catalog_ref`` doubles as the
production CPU/fallback path of the tile-catalog executor — a batched
matmul over dynamic-sliced strips, shape-stable, shard_map-safe."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pair_sim import COMPACT_CAPACITY, SCORE_PRECISION

__all__ = ["pair_scores_ref", "pair_scores_catalog_ref",
           "pair_scores_catalog_raw_ref", "pair_scores_catalog_compact_ref",
           "pack_survivor_mask",
           "grouped_matmul_ref", "attention_ref"]


def pair_scores_ref(a, b, *, threshold: float = 0.8, triangular: bool = False):
    """(M, d) × (N, d) → thresholded score matrix (M, N)."""
    s = jnp.einsum("md,nd->mn", a, b, precision=SCORE_PRECISION,
                   preferred_element_type=jnp.float32)
    keep = s >= threshold
    if triangular:
        m, n = s.shape
        rows = jnp.arange(m)[:, None]
        cols = jnp.arange(n)[None, :]
        keep = keep & (rows < cols)
    return jnp.where(keep, s, 0.0)


@functools.partial(
    jax.jit, static_argnames=("threshold", "block_m", "block_n"))
def pair_scores_catalog_ref(a, b, catalog, *, threshold: float = 0.8,
                            block_m: int = 128, block_n: int = 128):
    """jnp twin of kernels.pair_sim.pair_scores_catalog: vmap over catalog
    entries, each gathering its two strips with ``dynamic_slice`` (the
    BlockSpec-index_map analog) — XLA lowers the batch to one grouped
    matmul. Same (T, bm, bn) f32 0/1 output."""
    from .pair_sim import catalog_tile_mask

    m, d = a.shape
    n = b.shape[0]
    mp = -(-m // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    a_p = jnp.zeros((mp, d), a.dtype).at[:m].set(a)
    b_p = jnp.zeros((np_, d), b.dtype).at[:n].set(b)

    def one(entry):
        ai = jax.lax.dynamic_slice(a_p, (entry[0] * block_m, 0), (block_m, d))
        bi = jax.lax.dynamic_slice(b_p, (entry[1] * block_n, 0), (block_n, d))
        s = jax.lax.dot_general(
            ai, bi, (((1,), (1,)), ((), ())), precision=SCORE_PRECISION,
            preferred_element_type=jnp.float32)
        gi = entry[0] * block_m + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        gj = entry[1] * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = (s >= threshold) & catalog_tile_mask(entry, gi, gj)
        return keep.astype(jnp.float32)

    return jax.vmap(one)(catalog)


def pair_scores_catalog_raw_ref(a, b, catalog, *, block_m: int = 128,
                                block_n: int = 128):
    """UNthresholded, UNmasked per-tile scores — the model-parallel
    partial-score path: each model shard holds a (rows, d/n_model) panel,
    so its dots are *partial sums* and neither the threshold nor the
    catalog predicates can be applied until a ``psum`` over the model
    axis combines them. Same dynamic-slice batched matmul as
    :func:`pair_scores_catalog_ref`, returning raw (T, bm, bn) f32 —
    shard_map-safe (no jit wrapper: the caller's shard body is the jit
    unit, and the post-psum threshold+mask epilogue lives there)."""
    m, d = a.shape
    n = b.shape[0]
    mp = -(-m // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    a_p = jnp.zeros((mp, d), a.dtype).at[:m].set(a)
    b_p = jnp.zeros((np_, d), b.dtype).at[:n].set(b)

    def one(entry):
        ai = jax.lax.dynamic_slice(a_p, (entry[0] * block_m, 0), (block_m, d))
        bi = jax.lax.dynamic_slice(b_p, (entry[1] * block_n, 0), (block_n, d))
        return jax.lax.dot_general(
            ai, bi, (((1,), (1,)), ((), ())), precision=SCORE_PRECISION,
            preferred_element_type=jnp.float32)

    return jax.vmap(one)(catalog)


def pack_survivor_mask(masks, capacity: int):
    """Dense (T, bm, bn) survivor masks → the kernel's ``(packed,
    counts)`` contract, via an inclusive row-major cumsum (pack slot =
    rank − 1) and a batched scatter with a dump slot at ``capacity`` that
    absorbs overflow survivors. Slots beyond min(count, capacity) stay 0,
    matching the Pallas epilogue exactly. Shared by
    :func:`pair_scores_catalog_compact_ref` and the model-sharded scorer
    (which must pack *after* its cross-shard psum)."""
    t = masks.shape[0]
    p = masks.shape[1] * masks.shape[2]
    flat = masks.reshape(t, p) > 0
    cum = jnp.cumsum(flat.astype(jnp.int32), axis=1)
    counts = cum[:, -1:]
    dest = jnp.where(flat, jnp.minimum(cum - 1, capacity), capacity)
    pos = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32), (t, p))
    packed = jnp.zeros((t, capacity + 1), jnp.int32)
    packed = packed.at[jnp.arange(t)[:, None], dest].set(
        jnp.where(flat, pos, 0))
    return packed[:, :capacity], counts


@functools.partial(
    jax.jit, static_argnames=("threshold", "block_m", "block_n", "capacity"))
def pair_scores_catalog_compact_ref(a, b, catalog, *, threshold: float = 0.8,
                                    block_m: int = 128, block_n: int = 128,
                                    capacity: int = COMPACT_CAPACITY):
    """jnp twin of pair_sim.pair_scores_catalog_compact: same
    ``(packed, counts)`` contract — the mask from
    :func:`pair_scores_catalog_ref` packed by
    :func:`pack_survivor_mask`."""
    masks = pair_scores_catalog_ref(a, b, catalog, threshold=threshold,
                                    block_m=block_m, block_n=block_n)
    return pack_survivor_mask(masks, capacity)


def grouped_matmul_ref(x, tile_expert, w, *, block_t: int = 128):
    """x: (T, d) tile-aligned expert-sorted tokens; w: (E, d, F)."""
    t, _ = x.shape
    expert_of_token = jnp.repeat(tile_expert, block_t)
    w_tok = w[expert_of_token]                       # (T, d, F)
    return jnp.einsum("td,tdf->tf", x, w_tok,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def attention_ref(q, k, v, *, causal: bool = True, scale: float | None = None):
    """q: (B, H, S, D); k, v: (B, KVH, S, D). Plain softmax attention."""
    b, h, s, d = q.shape
    kvh = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(p.dtype)).astype(q.dtype)
