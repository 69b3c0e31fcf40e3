"""Mixture-of-Experts trunk (qwen3-moe / granite-moe).

Attention is the dense GQA block; the FFN is top-k routed over E experts.
Two dispatch modes:

  * ``gshard`` (default) — capacity-based one-hot dispatch/combine
    einsums. Static shapes, differentiable, EP-shardable (experts over
    the ``model`` mesh axis → GSPMD lowers the dispatch to all_to_all).
    Capacity = ⌈top_k·T/E⌉·capacity_factor per expert; overflow drops
    (standard GShard semantics).

  * ``grouped`` — the paper-technique path: tokens are *sorted by
    expert* (the expert-load bincount is the BDM analog, experts =
    blocks, tokens = entities) and pushed through the Pallas grouped
    GEMM (kernels/grouped_mm.py) with tile-aligned segments. Skew in
    tokens-per-expert becomes tile-count skew, which the kernel absorbs
    without capacity drops — the MoE incarnation of BlockSplit's "split
    large blocks into fixed-size work units". Used on the serving path
    and in tests; training keeps gshard for differentiability.

Auxiliary load-balancing loss (Switch-style): E · Σ_e f_e · p_e, where
f_e is the token fraction and p_e the mean router prob of expert e.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..sharding import act_constrain, constrain
from .config import ModelConfig
from .layers import (apply_rope, dense_init, dtype_of, gqa_attention,
                     gqa_attention_cached, rms_norm, rope_tables,
                     stack_layers)
from . import transformer as _tf

__all__ = ["init", "forward", "init_cache", "prefill", "decode_step",
           "moe_ffn", "router_aux_loss"]


def _init_layer(key, cfg: ModelConfig):
    d, hd, h, kv = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    e, fe = cfg.num_experts, cfg.d_expert
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 9)
    p = {
        "ln_attn": jnp.ones((d,), dt),
        "wq": dense_init(ks[0], (d, h * hd), dt),
        "wk": dense_init(ks[1], (d, kv * hd), dt),
        "wv": dense_init(ks[2], (d, kv * hd), dt),
        "wo": dense_init(ks[3], (h * hd, d), dt),
        "ln_mlp": jnp.ones((d,), dt),
        "router": dense_init(ks[4], (d, e), dt),
        "experts": {
            "w_gate": dense_init(ks[5], (e, d, fe), dt),
            "w_up": dense_init(ks[6], (e, d, fe), dt),
            "w_down": dense_init(ks[7], (e, fe, d), dt),
        },
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def init(cfg: ModelConfig, key) -> Dict:
    dt = dtype_of(cfg.param_dtype)
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    return {
        "embed": dense_init(k_emb, (cfg.vocab, cfg.d_model), dt, scale=1.0),
        "layers": stack_layers(lambda k: _init_layer(k, cfg), k_layers, cfg.n_layers),
        "ln_f": jnp.ones((cfg.d_model,), dt),
        "head": dense_init(k_head, (cfg.d_model, cfg.vocab), dt),
    }


# ---------------------------------------------------------------------------
# MoE FFN
# ---------------------------------------------------------------------------

def _route(p, x, cfg: ModelConfig):
    """x: (T, d) → (weights (T, k), expert_ids (T, k), probs (T, E))."""
    logits = jnp.einsum("td,de->te", x, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)   # renormalize
    return w.astype(x.dtype), ids, probs


_MOE_GROUP_TOKENS = 4096  # target tokens per dispatch group


def _experts_gshard(p, x, w, ids, cfg: ModelConfig):
    """Grouped capacity dispatch via batched sort+gather (GShard
    semantics, honest FLOPs, shardable). x: (T, d) -> (T, d).

    Two classic pitfalls are avoided:
      * one-hot dispatch einsums cost T*E*C*d FLOPs (~280x the useful
        expert GEMM at the 1M-token train cell) -- dispatch indices come
        from a per-group sort and the data moves through pure gathers
        (O(T*k*d), zero matmul FLOPs);
      * a single global scatter does not SPMD-partition (GSPMD
        replicates it -> hundreds of GiB per device) -- so tokens are
        reshaped into G groups of ~4k tokens, every dispatch op is
        *batched over G*, and G shards over the data axes. The expert
        buffer (G, E, C, d) is then constrained to E-over-``model`` --
        GSPMD lowers that reshard to the EP all_to_all.

    Capacity C = ceil(cf*k*Tg/E) per group; overflow drops first-come-
    first-served within the group exactly as in GShard (groups = the
    paper's input partitions, one more place its per-partition
    decomposition shows up).
    """
    t, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    g_count = max(1, t // _MOE_GROUP_TOKENS)
    while t % g_count:
        g_count -= 1
    tg = t // g_count
    cap = max(1, -(-int(cfg.capacity_factor * k * tg) // e))
    tk = tg * k

    # groups shard over EVERY mesh axis (dp AND model) — the dispatch-side
    # buffers scale 1/256, and the dp→EP reshard below stays an all_to_all
    xg = x.reshape(g_count, tg, d)
    xg = constrain(xg, ("pod", "data", "model"), None, None)
    ids_g = ids.reshape(g_count, tg, k)
    w_g = w.reshape(g_count, tg, k)

    flat_ids = ids_g.reshape(g_count, tk)
    order = jnp.argsort(flat_ids, axis=1, stable=True)        # (G, Tk)
    sorted_ids = jnp.take_along_axis(flat_ids, order, axis=1)
    counts = jax.vmap(lambda i: jnp.bincount(i, length=e))(flat_ids)  # (G, E)
    start = jnp.concatenate(
        [jnp.zeros((g_count, 1), counts.dtype),
         jnp.cumsum(counts, axis=1)[:, :-1]], axis=1)          # (G, E)
    pos = (jnp.arange(tk, dtype=jnp.int32)[None]
           - jnp.take_along_axis(start, sorted_ids, axis=1).astype(jnp.int32))
    keep_sorted = pos < cap                                    # (G, Tk)

    # dispatch gather: slot (e, c) <- sorted position start[e] + c
    gall = ("pod", "data", "model")
    slot_src = start[:, :, None] + jnp.arange(cap, dtype=start.dtype)  # (G,E,C)
    slot_valid = (jnp.arange(cap)[None, None, :]
                  < jnp.minimum(counts, cap)[:, :, None])
    slot_src = jnp.minimum(slot_src, tk - 1).reshape(g_count, e * cap)
    src_token = jnp.take_along_axis(
        order, slot_src.astype(order.dtype), axis=1) // k
    src_token = constrain(src_token, gall, None)
    xe = jnp.take_along_axis(xg, src_token[..., None], axis=1)  # (G, E*C, d)
    xe = xe * slot_valid.reshape(g_count, e * cap, 1).astype(xe.dtype)
    xe = constrain(xe, gall, None, None)
    xe = xe.reshape(g_count, e, cap, d)
    xe = constrain(xe, ("pod", "data"), "model", None, None)    # EP reshard
    gt = jnp.einsum("gecd,edf->gecf", xe, p["experts"]["w_gate"])
    ut = jnp.einsum("gecd,edf->gecf", xe, p["experts"]["w_up"])
    ye = jnp.einsum("gecf,efd->gecd", jax.nn.silu(gt) * ut,
                    p["experts"]["w_down"])                     # (G, E, C, d)
    ye = constrain(ye, ("pod", "data"), "model", None, None)

    # combine: loop the k slots (static) — gathers stay (G, Tg, d) so the
    # peak never holds the (G, T·k, d) replicated buffer, and every
    # G-batched tensor is pinned to G-over-all-axes
    ye_flat = constrain(ye.reshape(g_count, e * cap, d), gall, None, None)
    row = sorted_ids * cap + jnp.minimum(pos, cap - 1)          # (G, Tk)
    row = jnp.where(keep_sorted, row, e * cap - 1)
    inv = jnp.argsort(order, axis=1)                            # slot -> sorted pos
    w_flat = w_g.reshape(g_count, tk)
    y = jnp.zeros((g_count, tg, d), x.dtype)
    y = constrain(y, gall, None, None)
    for j in range(k):
        sorted_pos = inv[:, j::k]                               # (G, Tg)
        rows_j = jnp.take_along_axis(row, sorted_pos, axis=1)
        keep_j = jnp.take_along_axis(keep_sorted, sorted_pos, axis=1)
        y_j = jnp.take_along_axis(ye_flat, rows_j[..., None].astype(jnp.int32),
                                  axis=1)                        # (G, Tg, d)
        y_j = constrain(y_j, gall, None, None)
        scale = jnp.where(keep_j, w_flat[:, j::k], 0.0)
        y = y + y_j * scale[..., None].astype(y_j.dtype)
    return y.reshape(t, d).astype(x.dtype)


def _experts_grouped(p, x, w, ids, cfg: ModelConfig, impl: str = "auto"):
    """Sort-by-expert + Pallas grouped GEMM (tile-aligned, drop-free)."""
    from ..kernels import ops

    t, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    tk = t * k
    bt = 128 if tk >= 128 * e else 8   # small-batch decode: narrow tiles
    flat_ids = ids.reshape(tk)
    flat_w = w.reshape(tk)
    order = jnp.argsort(flat_ids, stable=True)
    x_rep = x[order // k]                                       # (T·k, d)
    # tile-aligned segments: worst case every expert pads one tile
    counts = jnp.bincount(flat_ids, length=e)
    tp = (-(-tk // bt) + e) * bt  # static upper bound on padded length
    padded = -(-counts // bt) * bt
    pstart = jnp.concatenate([jnp.zeros(1, padded.dtype), jnp.cumsum(padded)[:-1]])
    start = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    # destination row of each sorted token
    seg_of = jnp.searchsorted(jnp.cumsum(counts), jnp.arange(tk), side="right")
    dst = pstart[seg_of] + (jnp.arange(tk) - start[seg_of])
    xg = jnp.zeros((tp, d), x.dtype).at[dst].set(x_rep)
    tile_expert = jnp.minimum(jnp.searchsorted(
        jnp.cumsum(padded), jnp.arange(tp // bt) * bt, side="right"
    ), e - 1).astype(jnp.int32)  # clamp tail tiles past the real rows
    g = ops.grouped_matmul(xg, tile_expert, p["experts"]["w_gate"],
                           block_t=bt, impl=impl)
    u = ops.grouped_matmul(xg, tile_expert, p["experts"]["w_up"],
                           block_t=bt, impl=impl)
    yg = ops.grouped_matmul((jax.nn.silu(g) * u).astype(x.dtype), tile_expert,
                            p["experts"]["w_down"], block_t=bt, impl=impl)
    # yg[dst[i]] is the output of *sorted* slot i = original slot order[i]
    y_rep = yg[dst] * flat_w[order][:, None]                    # (T·k, d)
    out = jnp.zeros((t, d), x.dtype).at[order // k].add(y_rep)
    return out


def moe_ffn(p, x, cfg: ModelConfig):
    """x: (B, S, d) → (y, aux_loss)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    w, ids, probs = _route(p, xt, cfg)
    if cfg.moe_dispatch == "grouped":
        y = _experts_grouped(p, xt, w, ids, cfg)
    else:
        y = _experts_gshard(p, xt, w, ids, cfg)
    aux = router_aux_loss(ids, probs, cfg)
    return y.reshape(b, s, d), aux


def router_aux_loss(ids, probs, cfg: ModelConfig):
    e = cfg.num_experts
    f = jnp.mean(jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), axis=0)
    pbar = probs.mean(axis=0)
    return e * jnp.sum(f * pbar)


# ---------------------------------------------------------------------------
# Trunk
# ---------------------------------------------------------------------------

def _layer(x, p, cfg: ModelConfig, sin, cos):
    h = rms_norm(x, p["ln_attn"], cfg.rms_eps)
    q, k, v = _tf._qkv(p, cfg, h)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    attn = gqa_attention(q, k, v, causal=True, impl=cfg.attn_impl)
    b, s, _, _ = attn.shape
    x = x + jnp.einsum("bsh,hd->bsd", attn.reshape(b, s, -1), p["wo"])
    x = act_constrain(x, cfg.act_shard)
    h = rms_norm(x, p["ln_mlp"], cfg.rms_eps)
    y, aux = moe_ffn(p, h, cfg)
    return act_constrain(x + y, cfg.act_shard), aux, (k, v)


def forward(params, batch, cfg: ModelConfig, return_aux: bool = False):
    dt = dtype_of(cfg.compute_dtype)
    h = params["embed"][batch["tokens"]].astype(dt)
    s = h.shape[1]
    sin, cos = rope_tables(jnp.arange(s, dtype=jnp.int32), cfg.hd, cfg.rope_theta)

    def body(carry, p):
        x, aux_sum = carry
        y, aux, _ = _layer(x, p, cfg, sin, cos)
        return (y, aux_sum + aux), None

    body_fn = body
    if cfg.remat:
        body_fn = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    (h, aux), _ = jax.lax.scan(body_fn, (h, jnp.zeros((), jnp.float32)),
                               params["layers"],
                               unroll=cfg.scan_unroll(cfg.n_layers))
    h = rms_norm(h, params["ln_f"], cfg.rms_eps)
    logits = jnp.einsum("bsd,dv->bsv", h, params["head"].astype(h.dtype))
    if return_aux:
        return logits, aux / cfg.n_layers
    return logits


init_cache = _tf.init_cache


def prefill(params, batch, cfg: ModelConfig, cache):
    dt = dtype_of(cfg.compute_dtype)
    h = params["embed"][batch["tokens"]].astype(dt)
    s = h.shape[1]
    sin, cos = rope_tables(jnp.arange(s, dtype=jnp.int32), cfg.hd, cfg.rope_theta)

    def body(carry, p):
        y, _, (k, v) = _layer(carry, p, cfg, sin, cos)
        return y, (k, v)

    h, (ks, vs) = jax.lax.scan(body, h, params["layers"], unroll=cfg.scan_unroll(cfg.n_layers))
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    cache["pos"] = jnp.asarray(s, jnp.int32)
    h = rms_norm(h[:, -1:], params["ln_f"], cfg.rms_eps)
    return jnp.einsum("bsd,dv->bsv", h, params["head"].astype(h.dtype)), cache


def decode_step(params, tokens, cache, cfg: ModelConfig):
    dt = dtype_of(cfg.compute_dtype)
    h = params["embed"][tokens].astype(dt)
    pos = cache["pos"]
    sin, cos = rope_tables(pos[None], cfg.hd, cfg.rope_theta)

    def body(x, inp):
        p, k_cache, v_cache = inp
        hh = rms_norm(x, p["ln_attn"], cfg.rms_eps)
        q, k, v = _tf._qkv(p, cfg, hh)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))
        attn = gqa_attention_cached(q, k_cache, v_cache, pos + 1)
        b = attn.shape[0]
        x = x + jnp.einsum("bsh,hd->bsd", attn.reshape(b, 1, -1), p["wo"])
        hh = rms_norm(x, p["ln_mlp"], cfg.rms_eps)
        y, _ = moe_ffn(p, hh, cfg)
        return x + y, (k_cache, v_cache)

    h, (ks, vs) = jax.lax.scan(body, h, (params["layers"], cache["k"], cache["v"]),
                               unroll=cfg.scan_unroll(cfg.n_layers))
    cache = {"k": ks, "v": vs, "pos": pos + 1}
    h = rms_norm(h, params["ln_f"], cfg.rms_eps)
    return jnp.einsum("bsd,dv->bsv", h, params["head"].astype(h.dtype)), cache
