"""Distributed ER runtime: the paper's two MR jobs as shard_map programs.

Mapping (DESIGN.md §2): input partition Π_i ↔ per-device row shard on the
``data`` (× ``pod``) mesh axis; the shuffle ↔ ``all_gather`` on ICI; a
reduce task ↔ a work shard executed by one device. The number of logical
reduce tasks ``r`` stays decoupled from the device count ``n_dev`` exactly
as in the paper (r = 10·n there).

Job 1 (:func:`compute_bdm_sharded`): each device bincounts its local
blocking keys — its BDM *column* — then one ``all_gather`` produces the
full b × m matrix, replicated. This is Alg. 3 with the footnote-2 combiner
(the local bincount) built in.

Job 2 runs through the unified compiler (``er/compiler``): any plan
lowers to a tile catalog, the cost-LPT scheduler places tiles on
reducers and devices, and ``compiler.execute`` scores every shard
through the fused kernel. The entry points here — ``match_catalog_dist``
(self-join) and ``match_catalog_2src_dist`` (query-vs-corpus) — are thin
shims over that one executor, kept for their historical signatures;
Sorted Neighborhood on a mesh (the RepSN halo exchange) runs through
``run_er(mesh=...)``. Two genuinely different
legacy executors remain for comparison benchmarks:

  * :func:`match_pair_range_dist` — PairRange fully in-jit: every device
    derives its own pair list from the tiny replicated plan arrays via
    the closed-form inverse — the paper's map-side "relevant ranges"
    computation. No host-side pair materialization.
  * :func:`match_shards_hostplan` — per-device padded row-index arrays,
    O(P) host memory. The before-side of the catalog benchmarks.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.pair_range import PairRangePlan, pairs_of_range_jnp
from ..core.sorted_neighborhood import _w_eff
from .compiler import (DeviceKilledError, FaultEvent, FaultInjector,
                       FaultScript, NoHealthyDevicesError,
                       RecoveryFailedError, SupervisedReport, TileCatalog,
                       TransientScorerError, device_assignment, execute,
                       execute_supervised, make_scorer, pad_tiles,
                       shard_sane, tiles_for_devices)
from .compiler.comms import halo_bytes_per_device
from .compiler.execute import _score_and_compact
from .similarity import two_stage_match

__all__ = [
    "compute_bdm_sharded",
    # fault-tolerant runtime (shim passthrough over er/compiler)
    "DeviceKilledError",
    "FaultEvent",
    "FaultInjector",
    "FaultScript",
    "NoHealthyDevicesError",
    "RecoveryFailedError",
    "SupervisedReport",
    "TransientScorerError",
    "execute_supervised",
    "shard_sane",
    "match_catalog_dist",
    "match_catalog_2src_dist",
    "make_catalog_2src_scorer",
    "score_tiles_2src",
    "match_pair_range_dist",
    "match_shards_hostplan",
    "device_assignment",
    "plan_rows_for_devices",
    "plan_tiles_for_devices",
    "pad_device_tiles",
    "sn_replication_volume",
]


# ---------------------------------------------------------------------------
# Job 1: BDM
# ---------------------------------------------------------------------------

def compute_bdm_sharded(block_ids, num_blocks: int, mesh: Mesh,
                        axis: str = "data"):
    """block_ids: (n,) int32 sharded over ``axis``; one device shard = one
    input partition Π_i. Returns the replicated (b, m) BDM, m = axis size."""

    def job1(local_ids):
        col = jnp.bincount(local_ids.reshape(-1), length=num_blocks)
        cols = jax.lax.all_gather(col, axis)          # (m, b)
        return cols.T.astype(jnp.int32)               # (b, m)

    shard = jax.shard_map(job1, mesh=mesh, in_specs=P(axis), out_specs=P(),
                          check_vma=False)
    return shard(block_ids)


# ---------------------------------------------------------------------------
# Tile routing shims (scheduling lives in compiler/schedule.py)
# ---------------------------------------------------------------------------

def plan_tiles_for_devices(catalog: TileCatalog, n_dev: int,
                           healthy: Optional[np.ndarray] = None,
                           schedule=None) -> np.ndarray:
    """Partition a tile catalog over devices — see
    :func:`compiler.tiles_for_devices`. Without a schedule, reducers
    route round-robin via :func:`device_assignment` (the baseline)."""
    return tiles_for_devices(catalog, n_dev, healthy, schedule)


def pad_device_tiles(tiles_dev: np.ndarray, chunk: int) -> np.ndarray:
    """Pad the per-device tile cap UP to a multiple of ``chunk`` (>= one
    full chunk) with all-zero entries — the fixed-shape contract the
    resident service's recompile guard depends on
    (:func:`compiler.pad_tiles`)."""
    return pad_tiles(tiles_dev, chunk)


def plan_rows_for_devices(reducer_rows, r: int, n_dev: int,
                          healthy: Optional[np.ndarray] = None
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Concatenate per-reducer (rows_a, rows_b) into per-device padded
    arrays (n_dev, cap). Returns (rows_a, rows_b, valid). Feeds the
    legacy O(P) :func:`match_shards_hostplan` executor only."""
    dev_of = device_assignment(r, n_dev, healthy)
    per_dev_a = [[] for _ in range(n_dev)]
    per_dev_b = [[] for _ in range(n_dev)]
    for k in range(r):
        ra, rb = reducer_rows[k]
        d = int(dev_of[k])
        per_dev_a[d].append(np.asarray(ra, np.int32))
        per_dev_b[d].append(np.asarray(rb, np.int32))
    cat_a = [np.concatenate(x) if x else np.zeros(0, np.int32) for x in per_dev_a]
    cat_b = [np.concatenate(x) if x else np.zeros(0, np.int32) for x in per_dev_b]
    cap = max(1, max(a.shape[0] for a in cat_a))
    rows_a = np.zeros((n_dev, cap), np.int32)
    rows_b = np.zeros((n_dev, cap), np.int32)
    valid = np.zeros((n_dev, cap), bool)
    for d in range(n_dev):
        c = cat_a[d].shape[0]
        rows_a[d, :c] = cat_a[d]
        rows_b[d, :c] = cat_b[d]
        valid[d, :c] = True
    return rows_a, rows_b, valid


# ---------------------------------------------------------------------------
# Job 2: unified-executor shims
# ---------------------------------------------------------------------------

def match_catalog_dist(feats, catalog: TileCatalog, mesh: Mesh,
                       axis: str = "data", threshold: float = 0.8,
                       impl: str = "xla",
                       healthy: Optional[np.ndarray] = None,
                       chunk_tiles: int = 1024, schedule=None
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Stage 1 of any self-join plan on a mesh: features (n, d) f32 in the
    blocked layout, row-sharded over ``axis``; each device all_gathers
    them and scores its tile shard. Thin shim over
    :func:`compiler.execute` (mode "self"); pass ``schedule=`` for
    cost-LPT placement instead of the reducer round-robin. Returns the
    compacted stage-1 survivor candidates (rows_a, rows_b) as host int64
    arrays; run stage 2 with ``compiler.verify_pairs``."""
    return execute(catalog, feats, threshold=threshold, impl=impl,
                   mesh=mesh, axis=axis, healthy=healthy,
                   chunk_tiles=chunk_tiles, schedule=schedule)


def make_catalog_2src_scorer(mesh: Mesh, axis: str = "data", *,
                             threshold: float, block_m: int = 128,
                             block_n: int = 128, impl: str = "xla"):
    """ONE jitted sharded-index scorer for query-vs-corpus catalogs:
    corpus row-sharded and gathered, query batch replicated — see
    :func:`compiler.make_scorer` (mode "cross"). Build it once per
    resident service and reuse it for every micro-batch."""
    return make_scorer(mesh, axis, mode="cross", threshold=threshold,
                       block_m=block_m, block_n=block_n, impl=impl)


def score_tiles_2src(scorer, feats_a, feats_b, tiles_dev: np.ndarray,
                     chunk: int, bm: int, bn: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Drive a :func:`make_catalog_2src_scorer` over per-device tile
    shards, ``chunk`` tiles per device at a time (``tiles_dev`` must be
    pre-padded via :func:`pad_device_tiles` so every chunk has one shape),
    compacting each chunk's masks into global (rows_a, rows_b)."""
    return _score_and_compact(scorer, (feats_a, jnp.asarray(feats_b)),
                              tiles_dev, chunk, bm, bn)


def match_catalog_2src_dist(feats_a, feats_b, catalog: TileCatalog,
                            mesh: Mesh, axis: str = "data",
                            threshold: float = 0.8, impl: str = "xla",
                            healthy: Optional[np.ndarray] = None,
                            chunk_tiles: int = 1024, schedule=None
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """One-shot sharded-index cross matcher: stage 1 of a two-source
    catalog with the corpus (a-side) row-sharded over ``axis`` and the
    query batch (b-side) replicated. Builds a fresh scorer — resident
    services should hold a :func:`make_catalog_2src_scorer` instead and
    pass it to :func:`compiler.execute`."""
    return execute(catalog, feats_a, feats_b, threshold=threshold,
                   impl=impl, mesh=mesh, axis=axis, healthy=healthy,
                   chunk_tiles=chunk_tiles, schedule=schedule,
                   fixed_chunks=True)


def sn_replication_volume(n: int, w: int, n_dev: int, feature_dim: int,
                          itemsize: int = 4, per_hop: bool = False):
    """Job-2 interconnect bytes *received* across all devices:
    (boundary replication, full all-gather) — or, with ``per_hop``, the
    per-device hop-by-hop byte schedule of the multi-hop halo chain that
    ``run_er`` runs for SN on a mesh (``n`` divisible by ``n_dev``).

    RepSN replicates only the w−1 boundary rows between adjacent shards —
    O(n_dev · w · d) — where the generic executors all_gather the whole
    feature matrix, O(n_dev · n · d). The gap is the SN analog of the
    paper's map-output-replication accounting (Fig. 12). The accounting
    matches the executor at ANY window size: when w − 1 > n/n_dev the
    halo crosses ⌈(w−1)/n_loc⌉ shards via chained hops, but the last hop
    forwards only the final partial strip, so the total stays exactly
    n_dev · (w−1) · d · itemsize — ``per_hop=True`` returns the
    per-device hop list [n_loc·row_bytes, …, take·row_bytes] summing to
    (w−1) · d · itemsize (the 2-tuple form sums it across devices).
    """
    halo = _w_eff(n, w) - 1
    if n_dev <= 1:          # single device: the halo ppermute is a
        return ([] if per_hop else (0, 0))   # self-send — nothing
    n_loc = n // n_dev      # crosses the wire
    if per_hop:
        return halo_bytes_per_device(n_loc, halo, feature_dim, itemsize)
    return (n_dev * halo * feature_dim * itemsize,
            n_dev * (n - n_loc) * feature_dim * itemsize)


# ---------------------------------------------------------------------------
# Legacy executors (comparison baselines)
# ---------------------------------------------------------------------------

def _match_local(feats, codes, lens, ra, rb, valid, threshold, margin):
    mask, score = two_stage_match(
        feats[ra], feats[rb], codes[ra], lens[ra], codes[rb], lens[rb],
        threshold=threshold, filter_margin=margin)
    mask = mask & valid
    return mask, jnp.where(mask, score, 0.0)


def match_pair_range_dist(feats, codes, lens, plan: PairRangePlan,
                          mesh: Mesh, axis: str = "data",
                          threshold: float = 0.8, filter_margin: float = 0.25):
    """PairRange on a mesh, fully in-jit.

    feats (n, d) f32 / codes (n, L) uint8 / lens (n,) i32 are in the
    *blocked layout*, row-sharded over ``axis``. Every device owns the
    contiguous pair range [d·cap, (d+1)·cap) with cap = ⌈P/n_dev⌉ — the
    paper's eq. (2) with r = n_dev (additional logical ranges per device
    compose by concatenation since ranges are contiguous in p).

    Returns (rows_a, rows_b, mask, score), each (n_dev, cap), replicated
    row-block d holding device d's results.
    """
    n_dev = int(np.prod([mesh.shape[a] for a in (axis,)]))
    total = int(plan.total_pairs)
    cap = max(1, -(-total // n_dev))
    sizes = jnp.asarray(plan.block_sizes, jnp.int32)
    offsets = jnp.asarray(plan.offsets, jnp.int32)
    estart = jnp.asarray(plan.estart, jnp.int32)

    def job2(feats_l, codes_l, lens_l):
        d = jax.lax.axis_index(axis)
        feats_g = jax.lax.all_gather(feats_l, axis, tiled=True)
        codes_g = jax.lax.all_gather(codes_l, axis, tiled=True)
        lens_g = jax.lax.all_gather(lens_l, axis, tiled=True)
        lo = (d * cap).astype(jnp.int32)
        ra, rb, valid = pairs_of_range_jnp(sizes, offsets, estart, lo, cap, total)
        mask, score = _match_local(
            feats_g, codes_g, lens_g, ra, rb, valid, threshold, filter_margin)
        out = lambda x: x[None]  # (1, cap) per device → (n_dev, cap) stacked
        return out(ra), out(rb), out(mask), out(score)

    shard = jax.shard_map(job2, mesh=mesh,
                          in_specs=(P(axis), P(axis), P(axis)),
                          out_specs=(P(axis), P(axis), P(axis), P(axis)),
                          check_vma=False)
    return shard(feats, codes, lens)


def match_shards_hostplan(feats, codes, lens, rows_a, rows_b, valid,
                          mesh: Mesh, axis: str = "data",
                          threshold: float = 0.8, filter_margin: float = 0.25):
    """LEGACY executor: per-device padded row pairs (from
    :func:`plan_rows_for_devices`), row-sharded features — O(P) host
    memory. Kept as a comparison baseline; use :func:`match_catalog_dist`
    for the O(#tiles) fused path."""

    def job2(feats_l, codes_l, lens_l, ra, rb, v):
        feats_g = jax.lax.all_gather(feats_l, axis, tiled=True)
        codes_g = jax.lax.all_gather(codes_l, axis, tiled=True)
        lens_g = jax.lax.all_gather(lens_l, axis, tiled=True)
        mask, score = _match_local(
            feats_g, codes_g, lens_g, ra[0], rb[0], v[0],
            threshold, filter_margin)
        return mask[None], score[None]

    shard = jax.shard_map(
        job2, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)), check_vma=False)
    return shard(feats, codes, lens, rows_a, rows_b, valid)
