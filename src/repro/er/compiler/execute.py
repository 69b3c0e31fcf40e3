"""Execution: one generic driver for every lowered + scheduled catalog.

``execute(catalog, feats_a, ...)`` runs stage 1 (kernel cosine filter)
for ANY match job — single host or on a device mesh — and returns the
compacted survivor candidates; ``verify_pairs`` is the exact stage 2 and
``match_catalog`` fuses the two. The mesh path covers the three data
flows that used to be separate near-duplicate shard_map wrappers:

  * **self** — self-join: features row-sharded, each device all_gathers
    them and scores its tile shard (the shuffle of the paper's Job 2).
  * **cross** — two-source: the a-side (corpus) row-sharded and
    gathered, the b-side (query batch) replicated.
  * **halo** — RepSN: features row-sharded in sorted order, each device
    fetches only the ``halo`` boundary rows of the following shards via
    ⌈halo/n_loc⌉ chained neighbor ``ppermute`` hops (the last hop sends
    only the final partial strip) instead of all-gathering; tiles are in
    shard-local coordinates and ``base`` shifts survivors back to
    global rows.

The self/cross gathers take a ``comms`` policy (see ``compiler.comms``):
``"flat"`` is the all_gather above; ``"ring"`` assembles only the
``hops`` forward strips a device's tiles actually read via chained
``ppermute``; ``"hierarchical"`` runs an intra-group ring then
inter-group panel hops. Both rely on the planner's locality tile
placement and buffer-local tile rewrite — ``execute(comms=...)`` wires
all of it. A ``model_axis`` additionally column-shards the features:
each device scores (n_loc, d/n_model) panels into *partial* tile scores
and a ``psum`` over ``model`` combines them before the threshold +
catalog-predicate epilogue (which is meaningless on partials). Every
gather/hop/psum's bytes-received-per-device land in
``stage1_stats["interconnect"]``.

Both stages bracket their host work in spans (``er/trace.py``): per
stage 1, ``er.stage1.upload``; per stage-1 chunk, ``er.stage1.launch``
(tiles, padded), ``er.stage1.sync`` and ``er.stage1.decode``
(survivors); per stage-2 chunk, ``er.stage2.gather`` (pairs) and
``er.stage2.sync``.

``make_scorer`` builds the jitted per-shard scorer ONCE — resident
services hold one and reuse it for every micro-batch (jit caches by
function identity, so a per-call closure would retrace every batch).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ...kernels.ops import resolve_impl
from ...kernels.pair_sim import resolve_capacity
from ..trace import span
from .comms import (COMMS_POLICIES, CommsPlan, halo_bytes_per_device,
                    plan_comms, psum_bytes_per_device, rewrite_tiles_local)
from .faults import DeviceKilledError, FaultInjector, TransientScorerError
from .feedback import N_TILE_CLASSES, EwmaCostModel, tile_class
from .ir import A_TILE, B_TILE, NCOLS, R1, TileCatalog
from .lower import pad_tiles
from .schedule import (NoHealthyDevicesError, Schedule, schedule_tiles,
                       tile_costs, tiles_for_devices)

__all__ = [
    "CatalogScorer",
    "execute",
    "execute_supervised",
    "make_scorer",
    "score_catalog",
    "stage1_stats",
    "verify_pairs",
    "match_catalog",
    "shard_sane",
    "ShardRecord",
    "SupervisedReport",
    "RecoveryFailedError",
]


def _compact_on_device(impl: str) -> bool:
    """True when the resolved ``impl`` is a compiled backend, whose
    on-device packing epilogue beats a host mask scan. Interpret mode
    emulates the kernel in Python — the one-hot packing epilogue is
    O(bm·bn·capacity) numpy per tile there, so the dense mask
    (+ np.nonzero) is the honest path."""
    return impl in ("xla", "pallas")


def _pad_pow2(t: int, cap: int) -> int:
    p = 1
    while p < t:
        p *= 2
    return min(p, cap)


# ---------------------------------------------------------------------------
# Single-host stage 1
# ---------------------------------------------------------------------------

# Host-side instrumentation of stage 1 survivor decoding, keyed by path:
#   compact_decodes  — chunks decoded from the on-device packed epilogue
#   nonzero_decodes  — chunks decoded via the dense mask + np.nonzero
#   compact_overflows — compact chunks whose exact counts exceeded the
#                       capacity, forcing an exact mask-path fallback
# serve_bench asserts nonzero_decodes stays 0 across steady-state
# serving (the compaction epilogue replaced the host round-trip).
# "interconnect" accumulates bytes RECEIVED per device, per data flow,
# summed over kernel launches (each launch re-runs its gather), using
# the exact formulas of ``compiler.comms`` — mesh_bench asserts the
# ring/flat ratio on these counters.
stage1_stats: dict = {"compact_decodes": 0, "nonzero_decodes": 0,
                      "compact_overflows": 0, "survivors": 0,
                      "interconnect": {"flat_bytes": 0, "ring_bytes": 0,
                                       "hier_intra_bytes": 0,
                                       "hier_inter_bytes": 0,
                                       "halo_bytes": 0, "psum_bytes": 0}}


def _decode_packed(packed: np.ndarray, counts: np.ndarray,
                   chunk: np.ndarray, bm: int, bn: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Packed (T, capacity) survivor slots + exact (T,) counts → global
    (rows_a, rows_b), O(survivors) host work — no scan of dead cells."""
    tot = int(counts.sum())
    if tot == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    ti = np.repeat(np.arange(counts.size), counts)
    starts = np.cumsum(counts) - counts
    slot = np.arange(tot) - np.repeat(starts, counts)
    flat = packed[ti, slot].astype(np.int64)
    rows_a = chunk[ti, A_TILE].astype(np.int64) * bm + flat // bn
    rows_b = chunk[ti, B_TILE].astype(np.int64) * bn + flat % bn
    return rows_a, rows_b


def _survivors(out_a: List[np.ndarray], out_b: List[np.ndarray]
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Concatenate decoded stage-1 survivors, counted in
    ``stage1_stats["survivors"]``."""
    if not out_a:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    ra, rb = np.concatenate(out_a), np.concatenate(out_b)
    stage1_stats["survivors"] += ra.size
    return ra, rb


def score_catalog(feats_a, catalog: TileCatalog, feats_b=None, *,
                  threshold: float, impl: str = "auto",
                  chunk_tiles: int = 1024, compact: bool = True,
                  compact_capacity: Optional[int] = None
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Stage 1 for a whole catalog on one host: survivor candidate pairs.

    Runs the catalog through the kernel in fixed-size chunks (padded to
    powers of two so jit caches a handful of shapes) and compacts each
    chunk's survivors into global (row_a, row_b) indices. With
    ``compact`` (the default on the compiled xla/pallas paths) the
    compaction happens ON DEVICE — the kernel's prefix-sum epilogue
    returns packed slot ids + exact counts and the host decode is
    O(survivors); interpret mode keeps the dense-mask + ``np.nonzero``
    path (a Python emulator gains nothing from an emulated epilogue).

    ``compact_capacity`` bounds the packed slots per tile (default 1,024;
    either way capped at bm·bn by
    :func:`~...kernels.pair_sim.resolve_capacity`). Tiles whose EXACT
    count exceeds it fall back to the mask path for that chunk — still
    exact, counted in ``stage1_stats['compact_overflows']``. Returns two
    int64 arrays.
    """
    from ...kernels import ops

    impl = resolve_impl(impl)
    if feats_b is None:
        feats_b = feats_a
    with span("stage1.upload"):
        fa = jnp.asarray(feats_a)
        fb = jnp.asarray(feats_b)
    tiles = catalog.tiles
    bm, bn = catalog.block_m, catalog.block_n
    t_total = tiles.shape[0]
    use_compact = compact and _compact_on_device(impl)
    capacity = resolve_capacity(bm, bn, compact_capacity)
    out_a, out_b = [], []
    for lo in range(0, t_total, chunk_tiles):
        chunk = tiles[lo:lo + chunk_tiles]
        live = chunk.shape[0]
        padded = _pad_pow2(live, chunk_tiles)
        with span("stage1.launch", tiles=live, padded=padded):
            if padded != live:
                # Empty entries: zero windows (r0 == r1) mask everything
                # out.
                pad = np.zeros((padded - live, NCOLS), np.int32)
                chunk = np.concatenate([chunk, pad], axis=0)
            chunk_j = jnp.asarray(chunk)
            if use_compact:
                packed, counts = ops.pair_scores_catalog_compact(
                    fa, fb, chunk_j, threshold=threshold,
                    block_m=bm, block_n=bn, capacity=capacity, impl=impl)
        if use_compact:
            with span("stage1.sync"):
                counts = np.asarray(counts).reshape(-1).astype(np.int64)
                fits = counts.max(initial=0) <= capacity
                if fits:
                    packed = np.asarray(packed)
            if fits:
                stage1_stats["compact_decodes"] += 1
                with span("stage1.decode", survivors=int(counts.sum())):
                    ra, rb = _decode_packed(packed, counts, chunk, bm, bn)
                out_a.append(ra)
                out_b.append(rb)
                continue
            # Exact counts flagged dropped survivors: re-score this
            # chunk through the dense mask (exactness over speed).
            stage1_stats["compact_overflows"] += 1
        with span("stage1.launch", tiles=live, padded=padded):
            mask = ops.pair_scores_catalog(
                fa, fb, chunk_j, threshold=threshold,
                block_m=bm, block_n=bn, impl=impl)
        with span("stage1.sync"):
            mask = np.asarray(mask)
        stage1_stats["nonzero_decodes"] += 1
        with span("stage1.decode") as sp:
            ti, ii, jj = np.nonzero(mask)
            sp.set_metadata(survivors=ti.size)
            out_a.append(chunk[ti, A_TILE].astype(np.int64) * bm + ii)
            out_b.append(chunk[ti, B_TILE].astype(np.int64) * bn + jj)
    return _survivors(out_a, out_b)


# ---------------------------------------------------------------------------
# Mesh stage 1
# ---------------------------------------------------------------------------

class CatalogScorer:
    """A jitted per-shard scorer plus the metadata
    :func:`_score_and_compact` needs to decode its output. ``compact``
    scorers return (packed, counts) from the kernel's on-device
    compaction epilogue; mask scorers return dense survivor masks.
    Callable like the bare jitted function (jit identity is preserved —
    the wrapped function is created exactly once), with a lazily built
    mask twin for the exact-fallback path on capacity overflow."""

    def __init__(self, fn, *, compact: bool, capacity: int, mask_factory):
        self._fn = fn
        self.compact = compact
        self.capacity = capacity
        self._mask_factory = mask_factory
        self._mask_twin = None

    def __call__(self, *operands):
        return self._fn(*operands)

    def mask_twin(self) -> "CatalogScorer":
        """The dense-mask scorer with identical routing — built (and
        jitted) only if an overflow ever forces the exact fallback."""
        if self._mask_twin is None:
            self._mask_twin = self._mask_factory()
        return self._mask_twin


def _raw_to_mask(total, tiles, bm: int, bn: int, threshold: float):
    """Threshold + catalog-predicate epilogue on COMBINED tile scores —
    the post-psum half of the model-parallel path (partial scores cannot
    be thresholded; see ``ref.pair_scores_catalog_raw_ref``)."""
    from ...kernels.pair_sim import catalog_tile_mask

    def one(entry, s):
        gi = entry[0] * bm + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        gj = entry[1] * bn + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = (s >= threshold) & catalog_tile_mask(entry, gi, gj)
        return keep.astype(jnp.float32)

    return jax.vmap(one)(tiles, total)


def make_scorer(mesh: Mesh, axis: str = "data", *, mode: str = "self",
                threshold: float, block_m: int = 128, block_n: int = 128,
                impl: str = "xla", halo: int = 0, compact: bool = False,
                capacity: Optional[int] = None, comms: str = "flat",
                hops: int = 0, group: int = 1, inter_hops: int = 0,
                model_axis: Optional[str] = None) -> CatalogScorer:
    """Build ONE jitted per-shard catalog scorer for the given data flow.

    mode="self":  scorer(feats_sharded, tiles_chunk)
    mode="cross": scorer(feats_a_sharded, feats_b_replicated, tiles_chunk)
    mode="halo":  scorer(feats_sharded, tiles_chunk) — ⌈halo/n_loc⌉
                  chained neighbor ppermute hops (full strips, then the
                  final partial strip) instead of an all-gather; tiles
                  index the [local ‖ halo] strip and each device
                  receives exactly ``halo`` rows.

    ``comms`` selects the self/cross gather (``compiler.comms``):
    "flat" all_gathers; "ring" runs ``hops`` chained forward ppermutes,
    assembling the contiguous strip window [d·n_loc, d·n_loc +
    (hops+1)·n_loc) — tiles must be rewritten to that buffer's local
    coordinates and placed by the planner's locality rule, which is what
    bounds ``hops``; "hierarchical" assembles each ``group``-strip panel
    with an intra-group ring (reordered to global row order with a roll
    by the device's in-group rank), then exchanges whole panels over
    ``inter_hops`` stride-``group`` hops. Hop counts are compile-time
    constants — resident services pin them and route plans needing more
    hops to a flat scorer instead of recompiling.

    ``model_axis`` column-shards the features (d/n_model per device):
    the gather assembles rows as usual (columns stay local), the kernel
    computes *partial* tile scores via the raw (unthresholded, unmasked)
    op, a ``psum`` over ``model_axis`` combines them, and the threshold
    + predicate epilogue runs on the combined scores — compaction then
    packs post-psum via ``ref.pack_survivor_mask``. Outputs are
    replicated over ``model`` (post-psum), so out_specs stay data-only.
    The psum reassociates the d-dimensional dot, so a score lying within
    float ulps OF THE THRESHOLD ITSELF can flip versus the single-axis
    path — data-axis comms policies by contrast reduce in the same
    order and are bit-exact against flat.

    Each returns (n_dev, chunk, bm, bn) survivor masks — or, with
    ``compact=True`` (compiled backends only; see
    :func:`_compact_on_device`), (n_dev, chunk, capacity) packed slot
    ids + (n_dev, chunk, 1) exact counts from the kernel's on-device
    compaction epilogue, so the host decode is O(survivors) with no
    ``np.nonzero``. ``capacity`` defaults as in :func:`score_catalog`;
    a chunk that overflows it re-scores through the mask twin. Build the
    scorer once per resident service / driver and
    reuse it: jit caches by the wrapped function's identity, so a
    per-call closure would retrace every batch.
    """
    from ...kernels import ops, ref

    cap = resolve_capacity(block_m, block_n, capacity)
    if comms not in COMMS_POLICIES:
        raise ValueError(f"unknown comms policy {comms!r}")
    if comms != "flat" and mode == "halo":
        raise ValueError("halo mode has its own neighbor exchange; "
                         "comms applies to self/cross gathers only")
    n_data = int(mesh.shape[axis])
    perm_fwd = [(s, (s - 1) % n_data) for s in range(n_data)]

    def _epilogue(mask):
        if compact:
            packed, counts = ref.pack_survivor_mask(mask, cap)
            return packed[None], counts[None]
        return mask[None]

    def _score(a, b, tiles_l):
        if model_axis is not None:
            raw = ops.pair_scores_catalog_raw(
                a, b, tiles_l[0], block_m=block_m, block_n=block_n,
                impl=impl)
            total = jax.lax.psum(raw, model_axis)
            return _epilogue(_raw_to_mask(total, tiles_l[0], block_m,
                                          block_n, threshold))
        if compact:
            packed, counts = ops.pair_scores_catalog_compact(
                a, b, tiles_l[0], threshold=threshold,
                block_m=block_m, block_n=block_n, capacity=cap, impl=impl)
            return packed[None], counts[None]
        mask = ops.pair_scores_catalog(
            a, b, tiles_l[0], threshold=threshold,
            block_m=block_m, block_n=block_n, impl=impl)
        return mask[None]

    def _gather(feats_l):
        if comms == "flat":
            return jax.lax.all_gather(feats_l, axis, tiled=True)
        if comms == "ring":
            # Hop k delivers strip d+k; the buffer is the contiguous
            # global row window starting at this device's own strip.
            parts, cur = [feats_l], feats_l
            for _ in range(hops):
                cur = jax.lax.ppermute(cur, axis, perm_fwd)
                parts.append(cur)
            return jnp.concatenate(parts, axis=0) if hops else feats_l
        g = group
        n_loc = feats_l.shape[0]
        perm_intra = [(s, (s // g) * g + ((s % g) - 1) % g)
                      for s in range(n_data)]
        perm_inter = [(s, (s - g) % n_data) for s in range(n_data)]
        parts, cur = [feats_l], feats_l
        for _ in range(g - 1):
            cur = jax.lax.ppermute(cur, axis, perm_intra)
            parts.append(cur)
        panel = jnp.concatenate(parts, axis=0)
        if g > 1:
            # Device G·g+p assembled [strip p, p+1, … (group-relative,
            # wrapped)]; roll by its in-group rank restores global row
            # order so the panel is one contiguous window for every
            # group member.
            p = jax.lax.axis_index(axis) % g
            panel = jnp.roll(panel, p * n_loc, axis=0)
        iparts, cur = [panel], panel
        for _ in range(inter_hops):
            cur = jax.lax.ppermute(cur, axis, perm_inter)
            iparts.append(cur)
        return jnp.concatenate(iparts, axis=0) if inter_hops else panel

    fspec = P(axis, model_axis) if model_axis else P(axis)
    out_specs = (P(axis), P(axis)) if compact else P(axis)
    if mode == "self":
        def job2(feats_l, tiles_l):
            feats_g = _gather(feats_l)
            return _score(feats_g, feats_g, tiles_l)
        in_specs = (fspec, P(axis))
    elif mode == "cross":
        bspec = P(None, model_axis) if model_axis else P()

        def job2(feats_l, feats_q, tiles_l):
            feats_g = _gather(feats_l)
            return _score(feats_g, feats_q, tiles_l)
        in_specs = (fspec, bspec, P(axis))
    elif mode == "halo":
        def job2(feats_l, tiles_l):
            if halo:
                n_loc = feats_l.shape[0]
                k_hops = -(-halo // n_loc)
                take = halo - (k_hops - 1) * n_loc
                # Chained forward hops: before hop k each device holds
                # strip d+k−1 and forwards it; the LAST hop sends only
                # the ``take``-row prefix, so bytes received per device
                # are exactly halo · row_bytes.
                parts, cur = [feats_l], feats_l
                for k in range(1, k_hops + 1):
                    send = cur if k < k_hops else cur[:take]
                    cur = jax.lax.ppermute(send, axis, perm_fwd)
                    parts.append(cur)
                feats_cat = jnp.concatenate(parts, axis=0)
            else:
                feats_cat = feats_l
            return _score(feats_cat, feats_cat, tiles_l)
        in_specs = (fspec, P(axis))
    else:
        raise ValueError(f"unknown scorer mode {mode!r}")

    fn = jax.jit(jax.shard_map(job2, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False))
    mask_factory = (
        (lambda: make_scorer(mesh, axis, mode=mode, threshold=threshold,
                             block_m=block_m, block_n=block_n, impl=impl,
                             halo=halo, compact=False, comms=comms,
                             hops=hops, group=group, inter_hops=inter_hops,
                             model_axis=model_axis))
        if compact else (lambda: None))
    return CatalogScorer(fn, compact=compact, capacity=cap,
                         mask_factory=mask_factory)


def _score_and_compact(shard, operands, tiles_dev, chunk: int,
                       bm: int, bn: int,
                       base_a: Optional[np.ndarray] = None,
                       base_b: Optional[np.ndarray] = None,
                       launch_flows=None
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Drive a jitted per-shard catalog scorer chunk by chunk and compact
    each chunk's output into global (rows_a, rows_b) — host memory stays
    O(n_dev · chunk · bm · bn) regardless of plan size.

    Compact scorers (:class:`CatalogScorer` with ``compact=True``, the
    default on compiled backends) decode the kernel's packed survivor
    slots per device — O(survivors) host work, no ``np.nonzero``; a tile
    whose exact count exceeds the capacity re-scores that chunk through
    the lazily built mask twin, exactness over speed. Both paths are
    counted in ``stage1_stats``. ``base_a``/``base_b`` (n_dev,) shift device-local
    tile coordinates to global rows on each side (the RepSN and
    ring/hierarchical local-coordinate paths — cross-mode ring shifts
    the a-side only, since the b operand was never rewritten); None
    means that side's tiles already carry global strip indices.
    ``launch_flows(chunk_size) -> {flow: bytes}`` is called once per
    scorer invocation (including mask-twin refires — every invocation
    re-runs its gather) and accumulated into
    ``stage1_stats["interconnect"]``."""
    cap = tiles_dev.shape[1]
    is_compact = getattr(shard, "compact", False)
    out_a, out_b = [], []

    def _account(csize: int) -> None:
        if launch_flows is None:
            return
        acc = stage1_stats["interconnect"]
        for k, v in launch_flows(csize).items():
            acc[k] = acc.get(k, 0) + v

    for lo in range(0, cap, chunk):
        part = tiles_dev[:, lo:lo + chunk]
        # Padding entries are all-zero rows; a live tile has r1 > 0.
        live = int(np.count_nonzero(part[..., R1]))
        padded = part.shape[0] * part.shape[1]
        scorer = shard
        if is_compact:
            _account(part.shape[1])
            with span("stage1.launch", tiles=live, padded=padded):
                packed, counts = shard(*operands, jnp.asarray(part))
            with span("stage1.sync"):
                counts = np.asarray(counts)[..., 0].astype(np.int64)
                fits = counts.max(initial=0) <= shard.capacity
                if fits:
                    packed = np.asarray(packed)
            if fits:
                stage1_stats["compact_decodes"] += 1
                with span("stage1.decode", survivors=int(counts.sum())):
                    for dd in range(part.shape[0]):
                        ra, rb = _decode_packed(packed[dd], counts[dd],
                                                part[dd], bm, bn)
                        off_a = base_a[dd] if base_a is not None else 0
                        off_b = base_b[dd] if base_b is not None else 0
                        out_a.append(off_a + ra)
                        out_b.append(off_b + rb)
                continue
            stage1_stats["compact_overflows"] += 1
            scorer = shard.mask_twin()
        _account(part.shape[1])
        with span("stage1.launch", tiles=live, padded=padded):
            masks = scorer(*operands, jnp.asarray(part))
        with span("stage1.sync"):
            masks = np.asarray(masks)
        stage1_stats["nonzero_decodes"] += 1
        with span("stage1.decode") as sp:
            d, ti, ii, jj = np.nonzero(masks)
            sp.set_metadata(survivors=d.size)
            off_a = base_a[d] if base_a is not None else 0
            off_b = base_b[d] if base_b is not None else 0
            out_a.append(off_a
                         + part[d, ti, A_TILE].astype(np.int64) * bm + ii)
            out_b.append(off_b
                         + part[d, ti, B_TILE].astype(np.int64) * bn + jj)
    return _survivors(out_a, out_b)


def _tiles_by_device(catalog: TileCatalog, n_dev: int,
                     device_of: np.ndarray) -> np.ndarray:
    """(n_dev, cap, NCOLS) tile shards from an explicit placement (the
    comms planner's locality rule), zero-padded like
    :func:`tiles_for_devices` (empty windows mask everything out)."""
    counts = np.bincount(device_of, minlength=n_dev)
    cap = max(int(counts.max(initial=0)), 1)
    out = np.zeros((n_dev, cap, NCOLS), np.int32)
    for d in range(n_dev):
        mine = catalog.tiles[device_of == d]
        out[d, :mine.shape[0]] = mine
    return out


def _launch_flows_factory(plan: Optional[CommsPlan], halo: int,
                          n_data: int, n_model: int, n_rows: int,
                          feature_dim: int, bm: int, bn: int):
    """Per-launch interconnect accounting for :func:`_score_and_compact`:
    ``flows(chunk_size) -> {flow: bytes received per device}``, mirroring
    ``compiler.comms`` exactly (the gather/halo flows are launch-size
    independent; the psum payload is the launched tile count)."""
    if n_data <= 1 and n_model <= 1:
        return None
    n_loc = -(-n_rows // n_data)
    d_loc = feature_dim // max(n_model, 1)

    def flows(csize: int) -> dict:
        out = {}
        if halo:
            out["halo_bytes"] = sum(
                halo_bytes_per_device(n_loc, halo, d_loc))
        elif plan is not None and plan.policy == "ring":
            out["ring_bytes"] = plan.hops * n_loc * d_loc * plan.itemsize
        elif plan is not None and plan.policy == "hierarchical":
            row = d_loc * plan.itemsize
            out["hier_intra_bytes"] = (plan.group - 1) * n_loc * row
            out["hier_inter_bytes"] = (plan.inter_hops * plan.group
                                       * n_loc * row)
        elif n_data > 1:
            out["flat_bytes"] = (n_data - 1) * n_loc * d_loc * 4
        if n_model > 1:
            out["psum_bytes"] = psum_bytes_per_device(n_model, csize, bm, bn)
        return out

    return flows


def execute(catalog: TileCatalog, feats_a, feats_b=None, *,
            threshold: float, impl: str = "auto",
            mesh: Optional[Mesh] = None, axis: str = "data",
            chunk_tiles: int = 1024,
            schedule: Optional[Schedule] = None,
            healthy: Optional[np.ndarray] = None,
            scorer=None, fixed_chunks: bool = False,
            halo: int = 0, base: Optional[np.ndarray] = None,
            compact: bool = True,
            compact_capacity: Optional[int] = None,
            comms: str = "flat",
            comms_plan: Optional[CommsPlan] = None,
            model_axis: Optional[str] = None
            ) -> Tuple[np.ndarray, np.ndarray]:
    """Stage 1 of ANY lowered catalog: compacted survivor candidates.

    Single host (``mesh=None``): chunked :func:`score_catalog` (comms
    and model_axis are mesh concepts and are ignored).
    On a mesh: tiles route to devices via the :class:`Schedule` (cost-LPT
    placement) or round-robin when none is given, and each device scores
    its shard through a :func:`make_scorer` data flow — "self" when
    ``feats_b`` is None, "cross" when it is given (b replicated), "halo"
    when ``halo > 0`` (RepSN boundary replication; implies self-join,
    ``base`` shifts local survivor coordinates to global rows; any
    window size — the scorer chains ⌈halo/n_loc⌉ hops).

    ``comms`` swaps the flat all-gather for the ring / hierarchical
    strip exchange: the plan (``comms_plan`` > ``schedule.comms`` >
    freshly planned from the catalog) carries the locality tile
    placement, hop counts and buffer origins; tiles are rewritten to
    buffer-local coordinates and the plan's ``base`` shifts survivors
    back (a-side only in cross mode). A plan that degraded to flat
    (``plan.fallback``) runs the flat path. Requires every device
    healthy — locality placement has no failover, degrade to flat for
    fault-tolerant runs. ``model_axis`` adds the second mesh axis:
    features column-sharded d/n_model, partial scores psum-combined
    in-scorer. Interconnect bytes per flow accumulate in
    ``stage1_stats["interconnect"]``.

    ``fixed_chunks=True`` pads every device shard UP to a ``chunk_tiles``
    multiple so each kernel launch has the exact shape (n_dev,
    chunk_tiles, NCOLS) — the resident service's recompile guard;
    the default shrinks the chunk to the shard cap for one-shot jobs.
    Pass ``scorer=`` to reuse a prebuilt :func:`make_scorer` (required
    for zero steady-state recompiles); with ``comms_plan`` the scorer's
    pinned hop count must cover the plan's (extra gathered strips are
    never referenced, so over-gathering is exact — just wasted bytes).

    Returns host int64 (rows_a, rows_b); run stage 2 via
    :func:`verify_pairs`.
    """
    if mesh is None:
        return score_catalog(feats_a, catalog, feats_b,
                             threshold=threshold, impl=impl,
                             chunk_tiles=chunk_tiles, compact=compact,
                             compact_capacity=compact_capacity)
    n_data = int(mesh.shape[axis])
    n_model = int(mesh.shape[model_axis]) if model_axis else 1
    bm, bn = catalog.block_m, catalog.block_n
    n_rows = int(feats_a.shape[0])
    feature_dim = int(feats_a.shape[1])

    plan = comms_plan
    if plan is None and schedule is not None:
        plan = getattr(schedule, "comms", None)
    if plan is None and comms != "flat":
        if halo:
            raise ValueError("halo mode has its own neighbor exchange; "
                             "comms must stay 'flat'")
        if healthy is not None and not bool(np.all(healthy)):
            raise ValueError("comms != 'flat' requires all devices healthy "
                             "(locality placement has no failover); run "
                             "degraded jobs with comms='flat'")
        plan = plan_comms(catalog, n_rows, n_data, policy=comms,
                          n_model=n_model, feature_dim=feature_dim,
                          self_join=feats_b is None)

    ring_like = plan is not None and plan.policy != "flat"
    if ring_like:
        tiles_dev = _tiles_by_device(catalog, n_data, plan.device_of_tile)
    else:
        tiles_dev = tiles_for_devices(catalog, n_data, healthy, schedule)
    if fixed_chunks:
        chunk = chunk_tiles
    else:
        chunk = min(chunk_tiles, max(tiles_dev.shape[1], 1))
    tiles_dev = pad_tiles(tiles_dev, chunk)
    base_a = base_b = base
    if ring_like:
        tiles_dev = rewrite_tiles_local(tiles_dev, plan.base, bm, bn,
                                        shift_b=feats_b is None)
        base_a = plan.base
        base_b = plan.base if feats_b is None else None
    if scorer is None:
        mode = "halo" if halo > 0 else ("cross" if feats_b is not None
                                        else "self")
        rimpl = resolve_impl(impl)
        scorer = make_scorer(mesh, axis, mode=mode, threshold=threshold,
                             block_m=bm, block_n=bn, impl=rimpl, halo=halo,
                             compact=compact and _compact_on_device(rimpl),
                             capacity=compact_capacity,
                             comms=plan.policy if plan is not None else "flat",
                             hops=plan.hops if plan is not None else 0,
                             group=plan.group if plan is not None else 1,
                             inter_hops=(plan.inter_hops
                                         if plan is not None else 0),
                             model_axis=model_axis)
    with span("stage1.upload"):
        operands = ((feats_a,) if feats_b is None
                    else (feats_a, jnp.asarray(feats_b)))
    flows = _launch_flows_factory(plan, halo, n_data, n_model, n_rows,
                                  feature_dim, bm, bn)
    return _score_and_compact(scorer, operands, tiles_dev, chunk, bm, bn,
                              base_a=base_a, base_b=base_b,
                              launch_flows=flows)


# ---------------------------------------------------------------------------
# Supervised stage 1: tile-granular fault recovery
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShardRecord:
    """One per-device-shard completion record, the supervisor's ledger."""
    round: int
    device: int
    tiles: int
    cost: int                  # live pairs the shard was responsible for
    status: str                # ok | killed | transient | timeout | corrupt
    elapsed: float             # REAL wall seconds of the shard call
    injected_delay: float = 0.0  # virtual straggle seconds (injector only)

    @property
    def busy(self) -> float:
        """Simulated device-busy seconds: real wall time plus the
        injected virtual delay. Deadlines, the makespan clock and the
        feedback model run on busy time; latency *statistics* must use
        ``elapsed`` so chaos scripts don't poison them."""
        return self.elapsed + self.injected_delay


@dataclass
class SupervisedReport:
    """What happened during one :func:`execute_supervised` run."""
    rounds: int = 0            # scheduling rounds executed (1 == quiet run)
    recovered_tiles: int = 0   # tiles that succeeded on a retry round
    planned_cost: int = 0      # live pairs the catalog plans
    scored_cost: int = 0       # live pairs covered by accepted shards
    lost_tiles: int = 0        # tiles never scored (degraded mode only)
    steals: int = 0            # mid-stream re-LPT events (slow devices)
    stolen_tiles: int = 0      # queued tiles moved off slow devices
    predicted_makespan_s: float = 0.0  # calibrated round-1 projection
    measured_makespan_s: float = 0.0   # Σ rounds max device busy-time
    records: List[ShardRecord] = field(default_factory=list)
    backoffs: List[float] = field(default_factory=list)
    healthy: Optional[np.ndarray] = None   # final device mask

    @property
    def retries(self) -> int:
        return max(self.rounds - 1, 0)

    @property
    def coverage(self) -> float:
        """Fraction of planned live pairs actually scored — 1.0 after a
        full recovery, < 1.0 only in degraded (partial) mode."""
        if self.planned_cost == 0:
            return 1.0
        return self.scored_cost / self.planned_cost


class RecoveryFailedError(RuntimeError):
    """Retries/deadline exhausted with tiles still unscored (and the
    caller did not opt into partial results). Carries the report."""

    def __init__(self, msg: str, report: SupervisedReport):
        super().__init__(msg)
        self.report = report


def shard_sane(rows_a: np.ndarray, rows_b: np.ndarray,
               n_a: int, n_b: int) -> bool:
    """Cheap survivor sanity check: paired 1-D int arrays, every index in
    bounds. Any corrupted shard from :meth:`FaultInjector.corrupt_output`
    fails this by construction; a real deployment would run the same
    check on rows coming back over the wire."""
    if rows_a.shape != rows_b.shape or rows_a.ndim != 1:
        return False
    if rows_a.size == 0:
        return True
    return bool((rows_a >= 0).all() and (rows_a < n_a).all()
                and (rows_b >= 0).all() and (rows_b < n_b).all())


def _sub_catalog(catalog: TileCatalog, idx: np.ndarray) -> TileCatalog:
    return TileCatalog(tiles=catalog.tiles[idx], block_m=catalog.block_m,
                       block_n=catalog.block_n, n_rows_a=catalog.n_rows_a,
                       n_rows_b=catalog.n_rows_b, r=catalog.r,
                       total_pairs=catalog.total_pairs)


def execute_supervised(catalog: TileCatalog, feats_a, feats_b=None, *,
                       threshold: float, n_dev: int = 1,
                       healthy: Optional[np.ndarray] = None,
                       impl: str = "auto", chunk_tiles: int = 1024,
                       policy: str = "cost_lpt",
                       injector: Optional[FaultInjector] = None,
                       shard_deadline: Optional[float] = None,
                       deadline: Optional[float] = None,
                       max_retries: int = 3, backoff: float = 0.05,
                       backoff_factor: float = 2.0, sleep=time.sleep,
                       partial: bool = False,
                       feedback: Optional[EwmaCostModel] = None,
                       steal_factor: Optional[float] = None,
                       steal_quantum: Optional[int] = None,
                       compact: bool = True,
                       compact_capacity: Optional[int] = None
                       ) -> Tuple[np.ndarray, np.ndarray, SupervisedReport]:
    """Stage 1 with tile-granular fault recovery over logical devices.

    The catalog's tiles are cost-LPT scheduled onto ``n_dev`` logical
    device shards (the host drives each shard through the kernel exactly
    as ``execute`` would on a mesh — on a real cluster each shard call
    is the per-device RPC). Every shard produces a completion record;
    shards that fail (killed device), time out (wall + injected latency
    > ``shard_deadline``), raise transiently, or return survivors that
    fail :func:`shard_sane` are DISCARDED, their device is masked out
    where the failure indicates device loss (kill/timeout), and ONLY the
    lost tiles are re-scheduled over the shrunken healthy mask — at most
    ``max_retries`` extra rounds with exponential backoff
    (``backoff * backoff_factor**k``, each sleep clamped to the
    remaining wall ``deadline``).

    **Runtime feedback.** Pass ``feedback=`` (an :class:`EwmaCostModel`)
    and every accepted shard call trains the model, and every round's
    ``schedule_tiles`` is calibrated by it (wall-clock-weighted tile
    packing, heterogeneous device placement). Pass ``steal_factor=`` to
    enable mid-stream work stealing: each device's round work is split
    into ``steal_quantum``-tile batches (one batch per device when
    unset), dispatch follows per-device virtual busy-time clocks (the
    idle-device-next simulation of a parallel fleet), and after every
    completed call a device whose projected finish exceeds
    ``steal_factor ×`` the fleet's median projection has its *queued*
    (never in-flight) batches re-placed greedily onto the
    fastest-projected other devices. Stolen tiles were not yet scored,
    so exactly-once merging is untouched.

    Survivors merge idempotently: the catalog covers each planned pair
    exactly once and results from failed shards are never merged, so
    re-executing a tile cannot double-count — the final
    ``np.unique`` over (row_a, row_b) makes recovery exactly-once at the
    match-set level even if a future policy merges late stragglers.

    ``deadline`` bounds the whole call (seconds); on exhaustion —
    or when retries run out, or every device dies — the call either
    raises :class:`RecoveryFailedError` / :class:`NoHealthyDevicesError`
    or, with ``partial=True``, returns what it has with
    ``report.coverage < 1`` (the service's graceful-degradation mode).

    Returns ``(rows_a, rows_b, report)`` — deduplicated host int64
    survivor candidates plus the :class:`SupervisedReport`.
    """
    t_start = time.perf_counter()
    if healthy is None:
        healthy = np.ones(n_dev, bool)
    healthy = np.asarray(healthy, bool).copy()
    if steal_factor is not None and feedback is None:
        feedback = EwmaCostModel(n_dev)
    costs = tile_costs(catalog)
    classes = tile_class(catalog) if feedback is not None else None
    report = SupervisedReport(planned_cost=int(costs.sum()), healthy=healthy)
    out_a: List[np.ndarray] = [np.zeros(0, np.int64)]
    out_b: List[np.ndarray] = [np.zeros(0, np.int64)]
    pending = np.arange(catalog.num_tiles, dtype=np.int64)
    n_a, n_b = catalog.n_rows_a, catalog.n_rows_b

    def _out_of_time() -> bool:
        return (deadline is not None
                and time.perf_counter() - t_start >= deadline)

    def _predict(dev: int, batch: np.ndarray) -> float:
        return feedback.predict_tiles(dev, costs[batch], classes[batch])

    def _steal_pass(queues, clocks) -> None:
        """Re-place every queued batch of over-projected devices onto the
        fastest-projected peers (greedy, largest batch first)."""
        proj = {}
        for k in clocks:
            proj[k] = clocks[k] + sum(_predict(k, b)
                                      for b in queues.get(k, ()))
        med = float(np.median(list(proj.values())))
        victims = [k for k in list(queues)
                   if proj[k] > steal_factor * max(med, 1e-9)]
        for v in victims:
            if len(clocks) < 2:
                return
            batches = queues.pop(v)
            report.steals += 1
            proj[v] = clocks[v]
            batches.sort(key=lambda b: -float(costs[b].sum()))
            for b in batches:
                dst = min((k for k in clocks if k != v),
                          key=lambda k: (proj[k] + _predict(k, b), k))
                queues.setdefault(dst, []).append(b)
                proj[dst] += _predict(dst, b)
                report.stolen_tiles += int(b.size)

    while pending.size:
        if report.rounds > max_retries or _out_of_time():
            break
        if report.rounds:                       # retry round: back off
            b = backoff * backoff_factor ** (report.rounds - 1)
            if deadline is not None:            # never sleep past deadline
                b = min(b, max(deadline - (time.perf_counter() - t_start),
                               0.0))
            report.backoffs.append(b)
            if b > 0:
                sleep(b)
            if _out_of_time():                  # re-check: sleep spent it
                break
        report.rounds += 1
        sub = _sub_catalog(catalog, pending)
        try:
            sched = schedule_tiles(sub, n_dev=n_dev, healthy=healthy,
                                   policy=policy, feedback=feedback)
        except NoHealthyDevicesError:
            if partial:
                break
            report.lost_tiles = int(pending.size)
            raise
        if report.rounds == 1 and sched.calibrated:
            report.predicted_makespan_s = float(np.max(sched.predicted_s))
        dev_of_tile = sched.reducer_device[sched.tile_reducer]
        lost: List[np.ndarray] = []
        # Per-device FIFO queues of quantum-sized batches plus virtual
        # busy-time clocks; dispatching to the min-clock device (lowest
        # id on ties) simulates a parallel fleet — with one batch per
        # device and zeroed clocks it reproduces the classic ascending-
        # device-order call sequence exactly.
        queues: dict = {}
        clocks: dict = {}
        for d in np.flatnonzero(healthy):
            d = int(d)
            clocks[d] = 0.0
            mine = pending[dev_of_tile == d]
            if mine.size == 0:
                continue
            if steal_quantum:
                queues[d] = [mine[lo:lo + steal_quantum]
                             for lo in range(0, mine.size, steal_quantum)]
            else:
                queues[d] = [mine]
        round_makespan = 0.0
        while queues:
            if _out_of_time():
                for q in queues.values():
                    lost.extend(q)
                queues.clear()
                break
            d = min(queues, key=lambda k: (clocks[k], k))
            mine = queues[d].pop(0)
            if not queues[d]:
                del queues[d]
            cost = int(costs[mine].sum())
            t0 = time.perf_counter()
            status, extra = "ok", 0.0
            ra = rb = None
            try:
                plan = injector.shard_call(d) if injector else None
                ra, rb = score_catalog(
                    feats_a, _sub_catalog(catalog, mine), feats_b,
                    threshold=threshold, impl=impl,
                    chunk_tiles=chunk_tiles, compact=compact,
                    compact_capacity=compact_capacity)
                if plan is not None:
                    extra = plan.delay
                    if plan.corrupt:
                        ra, rb = injector.corrupt_output(ra, rb, n_a, n_b)
            except DeviceKilledError:
                status = "killed"
            except TransientScorerError:
                status = "transient"
            elapsed = time.perf_counter() - t0
            busy = elapsed + extra
            if status == "ok":
                if shard_deadline is not None and busy > shard_deadline:
                    status = "timeout"          # straggler: discard output
                elif not shard_sane(ra, rb, n_a, n_b):
                    status = "corrupt"          # failed the sanity check
            report.records.append(ShardRecord(
                round=report.rounds, device=d, tiles=int(mine.size),
                cost=cost, status=status, elapsed=elapsed,
                injected_delay=extra))
            clocks[d] += busy
            round_makespan = max(round_makespan, clocks[d])
            if status == "ok":
                out_a.append(ra)
                out_b.append(rb)
                report.scored_cost += cost
                if report.rounds > 1:
                    report.recovered_tiles += int(mine.size)
                if feedback is not None and cost > 0:
                    feedback.observe(
                        d, np.bincount(classes[mine], weights=costs[mine],
                                       minlength=N_TILE_CLASSES), busy)
            else:
                lost.append(mine)
                if status in ("killed", "timeout"):
                    healthy[d] = False          # device-level failure
                    lost.extend(queues.pop(d, []))
                    clocks.pop(d, None)
            if (steal_factor is not None and feedback is not None
                    and feedback.observations >= 1
                    and queues and len(clocks) > 1):
                _steal_pass(queues, clocks)
        report.measured_makespan_s += round_makespan
        pending = (np.concatenate(lost) if lost
                   else np.zeros(0, np.int64))

    report.lost_tiles = int(pending.size)
    report.healthy = healthy
    if pending.size and not partial:
        raise RecoveryFailedError(
            f"{pending.size} tiles unscored after {report.retries} retries",
            report)
    ra = np.concatenate(out_a)
    rb = np.concatenate(out_b)
    if ra.size:                                 # exactly-once at the
        pairs = np.unique(np.stack([ra, rb], axis=1), axis=0)   # match level
        ra, rb = pairs[:, 0], pairs[:, 1]
    return ra, rb, report


# ---------------------------------------------------------------------------
# Stage 2 + the fused entry point
# ---------------------------------------------------------------------------

_VERIFY_CHUNK = 8_192


def verify_pairs(codes_a, lens_a, codes_b, lens_b, rows_a, rows_b,
                 threshold: float,
                 chunk: int = _VERIFY_CHUNK) -> Tuple[np.ndarray, np.ndarray]:
    """Stage 2: exact normalized edit similarity >= threshold on candidate
    row pairs, in fixed-size padded chunks (one jit compilation)."""
    from ..similarity import edit_similarity

    hit_a, hit_b = [], []
    for lo in range(0, rows_a.shape[0], chunk):
        with span("stage2.gather", pairs=min(chunk, rows_a.shape[0] - lo)):
            a = rows_a[lo:lo + chunk]
            b = rows_b[lo:lo + chunk]
            pad = chunk - a.shape[0]
            if pad:
                a = np.concatenate([a, np.zeros(pad, a.dtype)])
                b = np.concatenate([b, np.zeros(pad, b.dtype)])
            operands = codes_a[a], lens_a[a], codes_b[b], lens_b[b]
        with span("stage2.sync"):
            sim = np.array(edit_similarity(*operands))
        if pad:
            sim[chunk - pad:] = 0.0
        sel = np.flatnonzero(sim >= threshold)
        hit_a.append(a[sel])
        hit_b.append(b[sel])
    if not hit_a:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(hit_a), np.concatenate(hit_b)


def match_catalog(catalog: TileCatalog, feats_a, codes_a, lens_a, *,
                  feats_b=None, codes_b=None, lens_b=None,
                  threshold: float = 0.8, filter_margin: float = 0.25,
                  impl: str = "auto", mesh: Optional[Mesh] = None,
                  axis: str = "data", schedule: Optional[Schedule] = None,
                  chunk_tiles: int = 1024,
                  compact_capacity: Optional[int] = None,
                  comms: str = "flat",
                  comms_plan: Optional[CommsPlan] = None,
                  model_axis: Optional[str] = None
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Fused filter-and-verify: kernel stage 1 over the tile catalog,
    exact stage 2 on compacted survivors. Returns matched (rows_a, rows_b)
    — indices into the a-side (and b-side, if distinct) arrays.
    ``comms``/``comms_plan``/``model_axis`` pass through to
    :func:`execute` (mesh runs only)."""
    cand_a, cand_b = execute(
        catalog, feats_a, feats_b,
        threshold=threshold - filter_margin, impl=impl,
        mesh=mesh, axis=axis, schedule=schedule, chunk_tiles=chunk_tiles,
        compact_capacity=compact_capacity, comms=comms,
        comms_plan=comms_plan, model_axis=model_axis)
    if codes_b is None:
        codes_b, lens_b = codes_a, lens_a
    return verify_pairs(codes_a, lens_a, codes_b, lens_b,
                        cand_a, cand_b, threshold)
