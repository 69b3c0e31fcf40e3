"""End-to-end ER pipeline — the paper's Fig. 2 workflow on one host.

Job 1: blocking keys + block distribution matrix (BDM) — or, for
Sorted Neighborhood, the sort pass (no BDM: the band's pair count is a
pure function of (n, w), so there is no block skew to measure).

Job 2: strategy plan + reduce-phase matching (two-stage cosine-filter →
edit-distance verify), through ONE path for every strategy — the
unified match-job compiler (``er/compiler``):

    plan → plan_to_job → lower → schedule_tiles → execute → verify

The plan lowers to the MatchJob IR, tiles into an MXU catalog, the
cost-LPT scheduler places tiles by their exact live-pair counts
(``ERConfig.schedule_policy``; the reported imbalance lands on
``ERResult.schedule``), and the fused kernel scores the catalog.
``ERConfig.executor = "reference"`` keeps the original per-reducer
numpy loop (materialized pair lists + chunked ``np.einsum``) as the
parity oracle and the before/after benchmark baseline.

Entities without blocking keys (block id −1) follow the paper's
decomposition: match_B(R,R) over the keyed subset ∪ match_⊥(R, R_∅) via a
two-source cartesian job (§III, Appendix I preamble). SN has no match_⊥
job — every entity has a sort key.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..core import (
    blocked_layout,
    compute_bdm,
    entity_indices,
    plan_basic,
    plan_block_split,
    plan_pair_range,
    pairs_of_range,
)
from ..core.basic import BasicPlan
from ..core.block_split import BlockSplitPlan
from ..core.pair_range import (PairRangePlan, range_segments,
                               map_output_size as pair_range_map_output_size)
from ..core.sorted_neighborhood import (
    SortedNeighborhoodPlan,
    map_output_size as sn_map_output_size,
    pairs_of_band_range,
    plan_sorted_neighborhood,
)
from ..core.two_source import TwoSourceBDM, plan_pair_range_2src, pairs_of_range_2src
from .blocking import prefix_block_ids, sn_sort_order
from .encode import encode_titles, ngram_features
from .compiler import (apply_schedule, autotune, cross_job,
                       enumerate_task_pairs, execute, execute_supervised,
                       halo_hop_rows, lower, match_catalog, plan_to_job,
                       row_sum_tiles, schedule_tiles, sn_halo_job,
                       verify_pairs)
from .trace import span

__all__ = ["ERConfig", "ERResult", "run_er", "featurize", "cross_restrict"]

_CHUNK = 65_536


def featurize(titles: Sequence[str], cfg) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Shared featurization for the batch pipeline and the resident service:
    (codes, lens) for the exact stage-2 verifier plus the hashed n-gram
    filter features. ``cfg`` needs ``max_len`` and ``feature_dim``
    (ERConfig and ServiceConfig both qualify). Spans ``er.featurize.encode``
    and ``er.featurize.ngrams``, each with the row count ``n``."""
    with span("featurize.encode", n=len(titles)):
        codes, lens = encode_titles(titles, max_len=cfg.max_len)
    with span("featurize.ngrams", n=len(titles)):
        feats = ngram_features(codes, dim=cfg.feature_dim, lengths=lens)
    return codes, lens, feats


def cross_restrict(matches: Set[Tuple[int, int]],
                   n_left: int) -> Set[Tuple[int, int]]:
    """Restrict a ``run_er`` match set over ``left ++ right`` to cross
    pairs, re-based as (left_idx, right_local_idx) — exactly what an
    ``ERService`` holding ``left`` resident must return for queries
    ``right`` (the streaming ≡ batch equivalence oracle)."""
    return {(a, b - n_left) for a, b in matches if a < n_left <= b}


@dataclass
class ERConfig:
    strategy: str = "pair_range"       # basic | block_split | pair_range
                                       # | sorted_neighborhood
    r: int = 32                        # reduce tasks
    m: int = 8                         # map tasks / input partitions
    threshold: float = 0.8
    prefix_len: int = 3
    window: int = 10                   # SN sliding-window size w
    feature_dim: int = 256
    max_len: int = 64
    filter_margin: float = 0.25
    match_missing_keys: bool = True
    executor: str = "catalog"          # catalog | reference
    block_m: int = 128                 # catalog tile rows (MXU-aligned)
    block_n: int = 128                 # catalog tile cols
    tune_tiles: bool = False           # pick (block_m, block_n) per job
                                       # via compiler.autotune (catalog
                                       # executor; overrides block_m/n)
    kernel_impl: str = "auto"          # auto | pallas | interpret | xla
    schedule_policy: str = "cost_lpt"  # cost_lpt | round_robin
    comms: str = "flat"                # flat | ring | hierarchical —
                                       # the data-axis gather policy when
                                       # run_er is given a mesh (plans
                                       # that miss the ring preconditions
                                       # degrade to flat, reported on
                                       # ERResult.extra["comms_fallback"]);
                                       # SN on a mesh always exchanges
                                       # the w − 1-row halo instead, so
                                       # it does not apply there
    # ---- fault-tolerant execution (catalog executor only) ----
    supervised_devices: int = 0        # > 0: stage 1 through the supervisor
                                       # on N logical device shards
    max_retries: int = 3               # recovery rounds per supervised job
    shard_deadline_s: Optional[float] = None   # straggler cutoff per shard
    backoff_s: float = 0.0             # base retry backoff (exponential)
    # ---- runtime feedback (supervised catalog executor only) ----
    steal_factor: Optional[float] = None   # > 0: mid-stream work stealing
    steal_quantum: Optional[int] = None    # tiles per dispatch batch
    # ---- stage-1 survivor compaction (catalog executor) ----
    compact_capacity: Optional[int] = None  # packed slots per tile; None
                                            # = pair_sim.resolve_capacity


@dataclass
class ERResult:
    matches: Set[Tuple[int, int]]
    total_pairs: int
    reducer_pairs: np.ndarray          # (r,) planned pair loads
    map_output_size: int               # kv-pairs emitted by map (Fig. 12)
    bdm_seconds: float                 # Job-1 time (BDM, or the SN sort)
    reducer_seconds: np.ndarray        # (r,) matching time: measured per
                                       # reducer by the reference executor;
                                       # the catalog executor's measured
                                       # total split by planned load
    extra: Dict = field(default_factory=dict)
    config: Optional[ERConfig] = None  # the (fresh) config this run used
    schedule: Optional[Dict] = None    # compiler Schedule.stats() (catalog
                                       # executor): reducer/device imbalance
    attempts: int = 1                  # supervisor rounds (1 == quiet run)
    recovered_tiles: int = 0           # tiles re-executed after a failure
    coverage: float = 1.0              # live pairs scored / planned
    steals: int = 0                    # work-stealing events (supervised)
    measured_makespan_s: float = 0.0   # supervisor busy-time makespan


_VERIFY_CHUNK = 8_192


def _match_pairs_chunked(feats, codes, lens, rows_a, rows_b,
                         threshold, margin) -> Tuple[np.ndarray, np.ndarray]:
    """REFERENCE executor (``ERConfig.executor = "reference"``): filter-
    and-verify over materialized (rows_a, rows_b). Stage 1 is a host
    ``np.einsum`` paired dot; stage 2 the exact verifier. Kept as the
    parity oracle for the compiler path and as the before-side of the
    kernel benchmark — the hot path no longer runs through here."""
    from .similarity import edit_similarity

    n = rows_a.shape[0]
    cand_a, cand_b = [], []
    for lo in range(0, n, _CHUNK):  # stage 1: numpy paired dots
        a = rows_a[lo:lo + _CHUNK]
        b = rows_b[lo:lo + _CHUNK]
        cos = np.einsum("pd,pd->p", feats[a], feats[b])
        sel = np.flatnonzero(cos >= threshold - margin)
        cand_a.append(a[sel])
        cand_b.append(b[sel])
    ca = np.concatenate(cand_a) if cand_a else np.zeros(0, np.int64)
    cb = np.concatenate(cand_b) if cand_b else np.zeros(0, np.int64)

    hit_a, hit_b = [], []
    for lo in range(0, ca.shape[0], _VERIFY_CHUNK):  # stage 2: exact verify
        a = ca[lo:lo + _VERIFY_CHUNK]
        b = cb[lo:lo + _VERIFY_CHUNK]
        pad = _VERIFY_CHUNK - a.shape[0]
        if pad:
            a = np.concatenate([a, np.zeros(pad, a.dtype)])
            b = np.concatenate([b, np.zeros(pad, b.dtype)])
        sim = np.array(edit_similarity(codes[a], lens[a], codes[b], lens[b]))
        if pad:
            sim[_VERIFY_CHUNK - pad:] = 0.0
        sel = np.flatnonzero(sim >= threshold)
        hit_a.append(a[sel])
        hit_b.append(b[sel])
    if not hit_a:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(hit_a), np.concatenate(hit_b)


def _reference_reducer_rows(plan, r: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Materialized per-reducer (rows_a, rows_b) for the reference
    executor — the O(P) path the compiler's catalog replaces. Pair
    enumeration is the compiler's (``enumerate_task_pairs``), so the
    triangular/rect logic exists exactly once in the codebase."""
    rows: List[Tuple[np.ndarray, np.ndarray]] = [
        (np.zeros(0, np.int64), np.zeros(0, np.int64)) for _ in range(r)]

    def add(k, ra, rb):
        pa, pb = rows[k]
        rows[k] = (np.concatenate([pa, ra]), np.concatenate([pb, rb]))

    if isinstance(plan, PairRangePlan):
        for k in range(r):
            _, _, _, ra, rb = pairs_of_range(plan, k)
            rows[k] = (ra, rb)
    elif isinstance(plan, SortedNeighborhoodPlan):
        for k in range(r):
            ra, rb = pairs_of_band_range(plan, k)
            rows[k] = (ra, rb)
    elif isinstance(plan, BlockSplitPlan):
        for t in range(plan.task_block.shape[0]):
            ra, rb = enumerate_task_pairs(
                int(plan.task_a_start[t]), int(plan.task_a_len[t]),
                int(plan.task_b_start[t]), int(plan.task_b_len[t]),
                bool(plan.task_triangular[t]))
            add(int(plan.task_reducer[t]), ra, rb)
    elif isinstance(plan, BasicPlan):
        sizes = plan.block_sizes
        estart = np.concatenate([np.zeros(1, np.int64), np.cumsum(sizes)[:-1]])
        for k_blk in np.flatnonzero(sizes >= 2):
            ra, rb = enumerate_task_pairs(
                int(estart[k_blk]), int(sizes[k_blk]), 0, 0, True)
            add(int(plan.block_reducer[k_blk]), ra, rb)
    else:
        raise TypeError(f"no reference enumeration for {type(plan).__name__}")
    return rows


def run_er(titles: Sequence[str], config: Optional[ERConfig] = None,
           block_ids: Optional[np.ndarray] = None,
           fault_injector=None, feedback=None,
           mesh=None, axis: str = "data") -> ERResult:
    """Match a single source. ``block_ids`` overrides prefix blocking (used
    by the Fig. 9 skew study; ignored by ``strategy="sorted_neighborhood"``,
    which partitions a sliding window over the sort order, not blocks).

    ``config=None`` builds a fresh default ``ERConfig`` per call (a shared
    mutable default instance would leak mutations across calls); the
    resolved config is returned on ``ERResult.config``.

    With ``cfg.supervised_devices > 0`` (or a ``fault_injector``), the
    catalog executor's stage 1 runs through the fault-tolerant supervisor
    (``compiler.execute_supervised``) on that many logical device shards;
    ``ERResult.attempts`` / ``recovered_tiles`` / ``coverage`` report what
    recovery did. The recovery invariant — the match set equals the
    failure-free run for any injected failure sequence — is the
    supervisor's headline contract (DESIGN.md §Fault tolerance).

    ``feedback`` (an ``EwmaCostModel``, supervised runs only) calibrates
    every supervised schedule by measured shard latency and enables
    ``cfg.steal_factor`` work stealing; pass the same model across calls
    to keep its calibration. With ``cfg.steal_factor`` set and no model
    given, a fresh one is created for the run.

    ``mesh`` runs the main Job-2 catalog on real devices (catalog
    executor only) through ``compiler.execute``: rows shard over
    ``axis`` and ``cfg.comms`` picks the gather policy. SN takes
    RepSN's data flow there instead: each device scores the band pairs
    whose smaller sorted position lies in its shard
    (``compiler.sn_halo_job``) and receives only the w − 1 rows after
    it, over chained neighbour hops; ``ERResult.schedule`` reports that
    fixed placement. When the mesh has a ``model`` axis of size > 1
    (``sharding.make_er_mesh``), the feature dimension shards over it
    with in-scorer psum combination.
    Features are zero-padded to shard/tile-divisible sizes host-side
    (padding rows/columns are never referenced by catalog tiles and
    contribute 0 to every dot). The match_⊥ job is query-batch-sized
    and stays on the host path, as does the reference executor.

    Each phase runs in a host span (``er/trace.py``) under
    ``er.run_er``: ``er.featurize``, ``er.block``, ``er.bdm`` (not for
    SN), ``er.plan``, then on the catalog executor ``er.job``,
    ``er.lower``, ``er.schedule``, ``er.stage1``, ``er.stage2`` and
    ``er.collect``, and ``er.null_key`` when the match_⊥ job runs.
    """
    n = len(titles)
    cfg = config if config is not None else ERConfig()
    if cfg.executor not in ("catalog", "reference"):
        raise ValueError(f"unknown executor {cfg.executor!r}")
    supervised = cfg.supervised_devices > 0 or fault_injector is not None
    if supervised and cfg.executor != "catalog":
        raise ValueError("supervised execution requires executor='catalog'")
    if mesh is not None and supervised:
        raise ValueError("supervised execution drives logical shards "
                         "host-side; it cannot also run on a mesh")
    if mesh is not None and cfg.executor != "catalog":
        raise ValueError("mesh execution requires executor='catalog'")
    if supervised and feedback is None and cfg.steal_factor is not None:
        from .compiler import EwmaCostModel
        feedback = EwmaCostModel(max(cfg.supervised_devices, 1))

    if cfg.strategy not in ("basic", "block_split", "pair_range",
                            "sorted_neighborhood"):
        raise ValueError(f"unknown strategy {cfg.strategy!r}")
    with span("run_er", n=n, strategy=cfg.strategy):
        return _run_er(titles, n, cfg, block_ids, supervised,
                       fault_injector, feedback, mesh, axis)


def _run_er(titles, n, cfg, block_ids, supervised, fault_injector,
            feedback, mesh, axis) -> ERResult:
    """``run_er``'s body, inside its ``er.run_er`` span."""
    # ---- featurize once (shared by both jobs) ----
    with span("featurize", n=n):
        codes, lens, feats = featurize(titles, cfg)

    extra: Dict = {}
    null_idx: Optional[np.ndarray] = None
    sn = cfg.strategy == "sorted_neighborhood"

    # ---- Job 1: the ONLY strategy-aware stage ----
    if sn:
        # Job 1 is the sort (no BDM — the band's pair count is a pure
        # function of (n, w), so there is no block skew to measure), and
        # every entity has a sort key, so SN has no match_⊥ job.
        with span("block"):
            t0 = time.perf_counter()
            to_global = sn_sort_order(titles)
            bdm_seconds = time.perf_counter() - t0
    else:
        with span("block"):
            if block_ids is None:
                block_ids, _ = prefix_block_ids(titles, k=cfg.prefix_len)
            block_ids = np.asarray(block_ids, np.int64)

            # Input partitions: m contiguous row ranges (HDFS-split
            # analog).
            part_ids = np.minimum(
                np.arange(n, dtype=np.int64) * cfg.m // max(n, 1), cfg.m - 1)

            keyed = block_ids >= 0
            keyed_idx = np.flatnonzero(keyed)
            if (~keyed).any():
                null_idx = np.flatnonzero(~keyed)

        with span("bdm") as sp:
            t0 = time.perf_counter()
            kb = block_ids[keyed_idx]
            kp = part_ids[keyed_idx]
            num_blocks = int(kb.max()) + 1 if kb.size else 0
            sp.set_metadata(blocks=num_blocks)
            bdm = compute_bdm(kb, kp, num_blocks, cfg.m)
            eidx = entity_indices(kb, kp, bdm)
            bdm_seconds = time.perf_counter() - t0

            sizes = bdm.sum(axis=1)
            perm, _ = blocked_layout(kb, eidx, sizes)
            # perm[blocked_row] = row within keyed_idx → global entity ids.
            to_global = keyed_idx[perm]

    # ---- plan, and the features in the plan's row order ----
    with span("plan", rows=int(to_global.size)) as sp:
        if sn:
            plan = plan_sorted_neighborhood(n, cfg.window, cfg.r)
            map_out = sn_map_output_size(plan)
            extra.update(window=cfg.window, w_eff=plan.w_eff)
        elif cfg.strategy == "pair_range":
            plan = plan_pair_range(bdm, cfg.r)
            # Closed form (core/pair_range.map_output_size): array
            # operations over the O(r + b) (range, block) segments —
            # exact at any scale, so it is ALWAYS computed.
            segs = range_segments(plan)
            sp.set_metadata(segments=int(segs.shape[0]))
            map_out = pair_range_map_output_size(plan, segs)
        elif cfg.strategy == "block_split":
            plan = plan_block_split(bdm, cfg.r)
            map_out = plan.map_output_size()
        else:
            plan = plan_basic(bdm, cfg.r)
            map_out = plan.map_output_size()
        g_feats = feats[to_global]
        g_codes = codes[to_global]
        g_lens = lens[to_global]
    reducer_pairs = np.asarray(plan.reducer_pairs, np.int64)
    total = int(plan.total_pairs)

    # ---- Job 2: reduce-phase matching (one path for every strategy) ----
    matches: Set[Tuple[int, int]] = set()
    reducer_seconds = np.zeros(cfg.r)
    sched_report: Optional[Dict] = None
    attempts, recovered_tiles = 1, 0
    planned_cost, scored_cost = 0, 0
    steals, measured_makespan = 0, 0.0

    def _supervised_stage1(catalog, feats_a, feats_b=None):
        """Stage 1 through the fault-tolerant supervisor; folds the
        report into the run-level recovery accounting."""
        nonlocal attempts, recovered_tiles, planned_cost, scored_cost, \
            steals, measured_makespan
        ca, cb, rep = execute_supervised(
            catalog, feats_a, feats_b,
            threshold=cfg.threshold - cfg.filter_margin,
            n_dev=max(cfg.supervised_devices, 1), impl=cfg.kernel_impl,
            policy=cfg.schedule_policy, injector=fault_injector,
            shard_deadline=cfg.shard_deadline_s,
            max_retries=cfg.max_retries, backoff=cfg.backoff_s,
            feedback=feedback, steal_factor=cfg.steal_factor,
            steal_quantum=cfg.steal_quantum,
            compact_capacity=cfg.compact_capacity)
        attempts = max(attempts, rep.rounds)
        recovered_tiles += rep.recovered_tiles
        planned_cost += rep.planned_cost
        scored_cost += rep.scored_cost
        steals += rep.steals
        measured_makespan += rep.measured_makespan_s
        return ca, cb

    def _geometry(job) -> Tuple[int, int]:
        """Per-job tile geometry: the occupancy autotuner's pick when
        ``cfg.tune_tiles``, else the configured (block_m, block_n)."""
        if not cfg.tune_tiles:
            return cfg.block_m, cfg.block_n
        rep = autotune(job, d=cfg.feature_dim,
                       capacity=cfg.compact_capacity)
        extra.setdefault("tuned_geometry", {})[
            f"job{len(extra['tuned_geometry'])}"] = rep.geometry
        return rep.geometry

    if cfg.executor == "catalog":
        # The compiler pipeline: lower the plan to MXU tiles, place tiles
        # by exact live-pair cost (LPT), score them all on the kernel,
        # verify compacted survivors. No per-reducer loop exists anymore:
        # ``reducer_seconds`` splits the two stages' measured time by
        # planned load, so only its sum is a clock reading.
        n_dev = int(mesh.shape[axis]) if mesh is not None else 1
        halo_sn = sn and mesh is not None
        n_loc = -(-n // n_dev)
        with span("job") as sp:
            job = (sn_halo_job(plan, n_dev, n_loc) if halo_sn
                   else plan_to_job(plan))
            sp.set_metadata(tasks=job.num_tasks)
        with span("lower") as sp:
            catalog = lower(job, *_geometry(job))
            sp.set_metadata(tiles=catalog.num_tiles)
        extra["catalog_tiles"] = catalog.num_tiles
        exec_feats, model_axis, comms_plan = g_feats, None, None
        halo, base = 0, None
        with span("schedule", devices=n_dev) as sp:
            # Tiles whose cost takes the row sum, not the closed form.
            sp.set_metadata(rowsum_tiles=int(row_sum_tiles(catalog).sum()))
            if mesh is not None:
                n_model = (int(mesh.shape["model"])
                           if "model" in mesh.axis_names and axis != "model"
                           else 1)
                if n_model > 1:
                    model_axis = "model"
                # Zero-pad rows to shard×tile-aligned length (the halo
                # job's tiles are shard-local: n_loc rows a shard) and
                # columns to model-divisible width: catalog tiles only
                # reference real rows, and zero feature columns
                # contribute 0 to every dot.
                mult = n_dev * (1 if halo_sn else int(
                    np.lcm(catalog.block_m, catalog.block_n)))
                rows_p = -(-g_feats.shape[0] // mult) * mult
                cols_p = -(-g_feats.shape[1] // n_model) * n_model
                if (rows_p, cols_p) != g_feats.shape:
                    exec_feats = np.zeros((rows_p, cols_p), g_feats.dtype)
                    exec_feats[:g_feats.shape[0],
                               :g_feats.shape[1]] = g_feats
                if halo_sn:
                    halo = plan.w_eff - 1
                    base = np.arange(n_dev, dtype=np.int64) * n_loc
                    sp.set_metadata(halo=halo,
                                    hops=len(halo_hop_rows(n_loc, halo)))
                elif cfg.comms != "flat":
                    from .compiler import plan_comms
                    comms_plan = plan_comms(
                        catalog, rows_p, n_dev, policy=cfg.comms,
                        n_model=n_model, feature_dim=cols_p,
                        self_join=True)
                    if comms_plan.fallback:
                        extra["comms_fallback"] = comms_plan.fallback
            # The halo job's tiles index their own device's strip, so
            # its placement is its attribution: reducer = device.
            sched = schedule_tiles(catalog, n_dev=n_dev,
                                   policy=("round_robin" if halo_sn
                                           else cfg.schedule_policy),
                                   comms_plan=comms_plan)
            sched_report = sched.stats()
            scheduled = apply_schedule(catalog, sched)
        t0 = time.perf_counter()
        with span("stage1", tiles=catalog.num_tiles):
            if supervised:
                ca, cb = _supervised_stage1(scheduled, g_feats)
            else:
                ca, cb = execute(
                    scheduled, exec_feats,
                    threshold=cfg.threshold - cfg.filter_margin,
                    impl=cfg.kernel_impl, mesh=mesh, axis=axis,
                    schedule=sched if mesh is not None else None,
                    model_axis=model_axis, halo=halo, base=base,
                    compact_capacity=cfg.compact_capacity)
        with span("stage2", pairs=int(ca.size)):
            ha, hb = verify_pairs(g_codes, g_lens, g_codes, g_lens,
                                  ca, cb, cfg.threshold)
        elapsed = time.perf_counter() - t0
        with span("collect", matches=int(ha.size)):
            for a, b in zip(to_global[ha], to_global[hb]):
                matches.add((min(int(a), int(b)), max(int(a), int(b))))
        if total:
            reducer_seconds = (elapsed * reducer_pairs.astype(np.float64)
                               / total)
    else:
        for k, (ra, rb) in enumerate(_reference_reducer_rows(plan, cfg.r)):
            if ra.size == 0:
                continue
            t0 = time.perf_counter()
            ha, hb = _match_pairs_chunked(
                g_feats, g_codes, g_lens, ra, rb,
                cfg.threshold, cfg.filter_margin)
            reducer_seconds[k] = time.perf_counter() - t0
            for a, b in zip(to_global[ha], to_global[hb]):
                matches.add((min(int(a), int(b)), max(int(a), int(b))))

    # ---- match_⊥(R, R_∅): entities without blocking key vs everyone ----
    if cfg.match_missing_keys and null_idx is not None and null_idx.size:
        with span("null_key", rows=int(null_idx.size)):
            bdm2 = TwoSourceBDM(
                bdm_r=np.full((1, 1), n, np.int64),
                bdm_s=np.full((1, 1), null_idx.size, np.int64))
            plan2 = plan_pair_range_2src(bdm2, cfg.r)
            extra["null_key_pairs"] = plan2.total_pairs
            if cfg.executor == "catalog":
                xjob = cross_job(n, int(null_idx.size), cfg.r)
                cross = lower(xjob, *_geometry(xjob))
                if supervised:
                    ca, cb = _supervised_stage1(cross, feats,
                                                feats[null_idx])
                    ha, hb = verify_pairs(codes, lens, codes[null_idx],
                                          lens[null_idx], ca, cb,
                                          cfg.threshold)
                else:
                    ha, hb = match_catalog(
                        cross, feats, codes, lens,
                        feats_b=feats[null_idx], codes_b=codes[null_idx],
                        lens_b=lens[null_idx], threshold=cfg.threshold,
                        filter_margin=cfg.filter_margin, impl=cfg.kernel_impl,
                        compact_capacity=cfg.compact_capacity)
                for a, b in zip(ha, null_idx[hb]):
                    a, b = int(a), int(b)
                    if a != b:
                        matches.add((min(a, b), max(a, b)))
            else:
                for k in range(cfg.r):
                    _, _, _, rr, rs = pairs_of_range_2src(plan2, k)
                    if rr.size == 0:
                        continue
                    ha, hb = _match_pairs_chunked(
                        feats, codes, lens,
                        rr, null_idx[rs], cfg.threshold, cfg.filter_margin)
                    for a, b in zip(ha, hb):
                        a, b = int(a), int(b)
                        if a != b:
                            matches.add((min(a, b), max(a, b)))
            total += plan2.total_pairs

    return ERResult(
        matches=matches,
        total_pairs=int(total),
        reducer_pairs=reducer_pairs,
        map_output_size=int(map_out),
        bdm_seconds=bdm_seconds,
        reducer_seconds=reducer_seconds,
        extra=extra,
        config=cfg,
        schedule=sched_report,
        attempts=attempts,
        recovered_tiles=recovered_tiles,
        coverage=(scored_cost / planned_cost if planned_cost else 1.0),
        steals=steals,
        measured_makespan_s=measured_makespan,
    )
