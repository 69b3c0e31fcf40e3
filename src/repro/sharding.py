"""Sharding rules: param/activation PartitionSpecs over the production
mesh axes ("pod", "data", "model").

Strategy (DESIGN.md §3):
  * 2-D weight sharding — tensor-parallel dims over ``model``, FSDP over
    ("pod", "data") on the other large dim.
  * MoE experts — expert-parallel over ``model`` (experts/16 per group),
    FSDP over ("pod", "data") on d_model.
  * Activations — batch over ("pod", "data"); layer-boundary constraints
    only, GSPMD propagates inside the layer.
  * KV caches — batch over ("pod", "data"), kv-heads over ``model``
    (when divisible; GQA with few kv heads falls back to replicated
    heads — re-sharding the sequence axis instead is a §Perf hillclimb).

Rules are name-pattern based over the param pytree paths, so every model
family gets specs without per-model tables. ``constrain`` is a no-op
outside a mesh context, keeping single-device smoke tests mesh-free.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["constrain", "param_spec", "param_shardings", "batch_spec",
           "cache_spec", "cache_shardings", "make_er_mesh",
           "DP_AXES", "TP_AXIS"]

DP_AXES = ("pod", "data")
TP_AXIS = "model"


def make_er_mesh(n_data: int, n_model: int = 1) -> Mesh:
    """The ER executor's 2-D ``(data, model)`` mesh: corpus rows shard
    over ``data``, the hashed-n-gram feature dimension over ``model``
    (``compiler.execute(model_axis="model")`` psums the partial tile
    scores). Reuses the train substrate's axis names so the same mesh
    can carry both workloads; ``n_model=1`` is the classic 1-D data
    mesh every existing ER path runs on. Devices reshape row-major —
    the ``model`` axis varies fastest, keeping a model group's devices
    adjacent (the higher-bandwidth hop, same discipline as the dp×mp
    train meshes)."""
    devices = np.asarray(jax.devices())
    if devices.size < n_data * n_model:
        raise ValueError(f"need {n_data * n_model} devices for a "
                         f"({n_data}, {n_model}) mesh, "
                         f"have {devices.size}")
    grid = devices[:n_data * n_model].reshape(n_data, n_model)
    return Mesh(grid, ("data", TP_AXIS),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _active_axes() -> Tuple[str, ...]:
    m = jax.sharding.get_abstract_mesh()
    return tuple(m.axis_names) if m is not None and not m.empty else ()


def _filter_spec(spec: Tuple, axes: Tuple[str, ...]) -> P:
    """Drop mesh axes that do not exist in the active mesh (lets the same
    rules serve the (data, model) single-pod and (pod, data, model)
    multi-pod meshes and the 1-device test mesh)."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in axes)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in axes else None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def constrain(x, *spec):
    """with_sharding_constraint that no-ops outside a mesh context."""
    axes = _active_axes()
    if not axes:
        return x
    return jax.lax.with_sharding_constraint(x, _filter_spec(spec, axes))


def act_constrain(x, mode: str):
    """Layer-boundary activation constraint for a (B, S, d) tensor.

    "full_dp" puts the batch over EVERY mesh axis (pure ZeRO-style data
    parallelism) — the right layout for recurrent trunks (rwkv6/zamba2),
    whose time scans otherwise force per-layer sequence all-gathers."""
    if mode == "seq":
        return constrain(x, DP_AXES, TP_AXIS, None)
    if mode == "d":
        return constrain(x, DP_AXES, None, TP_AXIS)
    if mode == "full_dp":
        return constrain(x, DP_AXES + (TP_AXIS,), None, None)
    return constrain(x, DP_AXES, None, None)


def attn_logits_constrain(x):
    """Shard (B, G, KV, Q, S) attention logits over the model axis.

    Preference order: group dim (g-major head layout makes this the
    common case, e.g. qwen3-moe's 64h/4kv → g=16), kv dim (MHA), else
    the key/sequence dim (split-K — softmax partials psum'd by GSPMD).
    Without this, GQA head counts not divisible by tp leave the logits
    replicated — tens of GiB per chunk at 32k context."""
    axes = _active_axes()
    if not axes or TP_AXIS not in axes:
        return x
    tp = jax.sharding.get_abstract_mesh().shape[TP_AXIS]
    if tp <= 1:
        return x
    _, g, kv, _, s = x.shape
    if g % tp == 0:
        return constrain(x, DP_AXES, TP_AXIS, None, None, None)
    if kv % tp == 0:
        return constrain(x, DP_AXES, None, TP_AXIS, None, None)
    if s % tp == 0:
        return constrain(x, DP_AXES, None, None, None, TP_AXIS)
    return constrain(x, DP_AXES, None, None, None, None)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# (path-regex, spec for the *last* ndim dims). Layer stacking adds a
# leading None automatically. First match wins.
_RULES = [
    # --- MoE experts: (E, d, f) — EP over model, FSDP on d ---
    (r"experts.*(w_gate|w_up)", (TP_AXIS, DP_AXES, None)),
    (r"experts.*w_down", (TP_AXIS, None, DP_AXES)),
    (r"router", (DP_AXES, None)),
    # --- embeddings / lm head ---
    # embed: vocab over dp, d over model — a vocab-over-model lookup makes
    # GSPMD all-gather the full table per device (measured: ×26 f32 copies
    # of a 2.4 GiB table on qwen3-moe)
    (r"embed", (DP_AXES, TP_AXIS)),
    (r"(^|/)head", (DP_AXES, TP_AXIS)),
    (r"vis_proj", (DP_AXES, TP_AXIS)),
    # --- attention ---
    (r"wq|wk|wv|w_qkv", (DP_AXES, TP_AXIS)),
    (r"wo", (TP_AXIS, DP_AXES)),
    (r"b[qkv]$", (TP_AXIS,)),
    # --- dense FFN ---
    (r"w_gate|w_up|w_in|fc1", (DP_AXES, TP_AXIS)),
    (r"w_down|w_out|fc2", (TP_AXIS, DP_AXES)),
    # --- rwkv6 time-mix / channel-mix ---
    (r"(w_r|w_k|w_v|w_g)$", (DP_AXES, TP_AXIS)),
    (r"w_wkv_out", (TP_AXIS, DP_AXES)),
    (r"w_decay$", (DP_AXES, TP_AXIS)),
    (r"w_decay_b", (TP_AXIS,)),
    (r"cm_(k|r)", (DP_AXES, TP_AXIS)),
    (r"cm_v", (TP_AXIS, DP_AXES)),
    # --- mamba2 ---
    (r"in_proj", (DP_AXES, TP_AXIS)),
    (r"out_proj", (TP_AXIS, DP_AXES)),
    (r"conv_w", (TP_AXIS, None)),
    (r"(A_log|D$|dt_bias|conv_b)", (TP_AXIS,)),
]


def param_spec(path: str, ndim: int, stacked: int) -> P:
    """PartitionSpec for one param leaf given its flattened path.
    ``stacked`` = number of leading layer-stack dims (zamba2's blocks
    carry two: (n_super, every, ...))."""
    eff = ndim - stacked
    lead = (None,) * stacked
    for pat, spec in _RULES:
        if re.search(pat, path):
            if len(spec) == eff:
                return lead + tuple(spec)
            # bias-like reduced rank: keep the last axes of the rule
            if eff == 1 and len(spec) >= 1:
                return lead + (spec[-1],)
    return ((None,) * ndim)  # norms, scalars: replicated


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def param_shardings(params_shape: Any, mesh: Mesh):
    """Pytree of NamedShardings matching ``params_shape`` (a pytree of
    arrays or ShapeDtypeStructs)."""
    axes = tuple(mesh.axis_names)

    def one(path, leaf):
        ps = _path_str(path)
        stacked = 2 if "blocks" in ps else (1 if "layers" in ps else 0)
        spec = param_spec(ps, len(leaf.shape), stacked)
        # Never shard a dim that isn't divisible by the axis size.
        sized = []
        for dim, entry in zip(leaf.shape, spec):
            if entry is None:
                sized.append(None)
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            names = tuple(a for a in names if a in axes)
            size = int(np.prod([mesh.shape[a] for a in names])) if names else 1
            if names and dim % size == 0:
                sized.append(names if len(names) > 1 else names[0])
            else:
                sized.append(None)
        return NamedSharding(mesh, _filter_spec(tuple(sized), axes))

    return jax.tree_util.tree_map_with_path(one, params_shape)


_CACHE_RULES = [
    # (pattern, spec-for-trailing-dims after the leading layer-stack dim)
    (r"^(k|v|xk|xv)$", ("B", "S", TP_AXIS, None)),       # (L, B, S, KV, hd)
    (r"^wkv$", ("B", TP_AXIS, None, None)),              # (L, B, H, hd, hd)
    (r"^(tm_x|cm_x)$", ("B", TP_AXIS)),                  # (L, B, d)
    (r"^ssm$", (None, "B", TP_AXIS, None, None)),        # (nsup, every, B, nh, hd, ds)
    (r"^conv$", (None, "B", None, TP_AXIS)),             # (nsup, every, B, K-1, C)
]


def cache_shardings(cache_shape: Any, mesh: Mesh, batch_size: int):
    """NamedShardings for a serve cache pytree.

    'B' entries shard batch over (pod, data) when divisible; for the
    k/v caches, when the batch cannot shard (long_500k batch=1) the
    *sequence* dim takes the dp axes instead — context parallelism."""
    axes = tuple(mesh.axis_names)
    dp = int(np.prod([mesh.shape[a] for a in DP_AXES if a in axes]))
    tp = mesh.shape.get(TP_AXIS, 1) if TP_AXIS in axes else 1
    batch_ok = dp > 1 and batch_size % dp == 0

    def one(path, leaf):
        name = _path_str(path).split("/")[-1]
        for pat, rule in _CACHE_RULES:
            if not re.search(pat, name):
                continue
            spec = [None]  # leading layer-stack dim
            dims = leaf.shape[1:]
            if rule[0] == "B" and rule[1] == "S":   # k/v caches
                s_dim, h_dim = dims[1], dims[2]
                heads_ok = tp > 1 and h_dim % tp == 0
                # batch → dp; kv-heads → model when divisible, else the
                # sequence takes the model axis (flash-decoding split-K /
                # context parallelism) so a 32k cache never sits whole on
                # one chip; long_500k (batch 1) puts dp on the sequence.
                seq_axes = []
                if batch_ok:
                    spec.append(DP_AXES)
                else:
                    spec.append(None)
                    if dp > 1:
                        seq_axes += [a for a in DP_AXES if a in axes]
                if not heads_ok and TP_AXIS in axes and tp > 1:
                    seq_axes.append(TP_AXIS)
                sz = int(np.prod([mesh.shape[a] for a in seq_axes])) if seq_axes else 1
                if seq_axes and s_dim % sz == 0:
                    spec.append(tuple(seq_axes) if len(seq_axes) > 1 else seq_axes[0])
                else:
                    spec.append(None)
                spec.append(TP_AXIS if heads_ok else None)
                spec.append(None)
                return NamedSharding(mesh, _filter_spec(tuple(spec), axes))
            for dim, entry in zip(dims, rule):
                if entry == "B":
                    spec.append(DP_AXES if batch_ok else None)
                elif entry == "S":
                    spec.append(None)
                elif entry == TP_AXIS:
                    spec.append(TP_AXIS if (tp > 1 and dim % tp == 0) else None)
                else:
                    spec.append(None)
            return NamedSharding(mesh, _filter_spec(tuple(spec), axes))
        return NamedSharding(mesh, P())  # pos scalar etc.

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def batch_spec(mesh: Mesh) -> P:
    """Token batches: batch dim over (pod, data)."""
    return _filter_spec((DP_AXES,), tuple(mesh.axis_names))


def cache_spec(mesh: Mesh, n_kv_heads: int, batch_size: int) -> P:
    """KV cache (L, B, S, KV, hd): B over (pod, data) when divisible,
    kv-heads over model when divisible; else sequence over model."""
    axes = tuple(mesh.axis_names)
    dp = int(np.prod([mesh.shape[a] for a in DP_AXES if a in axes]))
    tp = mesh.shape.get(TP_AXIS, 1) if TP_AXIS in axes else 1
    b_entry = DP_AXES if batch_size % max(dp, 1) == 0 and dp > 1 else None
    if n_kv_heads % max(tp, 1) == 0 and tp > 1:
        spec = (None, b_entry, None, TP_AXIS, None)
    else:
        spec = (None, b_entry, TP_AXIS, None, None)  # context-parallel seq
    return _filter_spec(spec, axes)
