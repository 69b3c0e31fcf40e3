"""Tile-geometry autotuning: pick per-catalog (block_m, block_n) from an
MXU-aligned lattice by exact occupancy, refined online by wall clock.

The lowering quantum IS the load-balance floor (the paper balances at
sub-block granularity, §IV), and it is also the MXU-occupancy knob: a
skewed BDM's long tail of small blocks lowers into tiles that are mostly
dead cells at 128×128 — the kernel multiplies padding. The autotuner
closes DESIGN §Perf's "tighter tile sizes per block-size histogram"
hillclimb with two signals:

  * **Exact occupancy** (static): lower the job at each candidate
    geometry and take ``waste = T·bm·bn − Σ tile_costs``. The live-pair
    sum is *geometry-invariant* (it is the plan's pair total — only the
    dead padding moves), and ``tile_costs`` is exact, so the waste model
    equals enumerated dead cells by construction (property-tested in
    tests/test_tile_geometry.py). The static score adds per-tile strip
    DMA traffic and fixed grid-step overhead on top of the cell count:
    ``T·(bm·bn + beta·(bm+bn) + tile_overhead)`` — a roofline in
    cell-equivalents that keeps tiny tiles from winning on occupancy
    alone while drowning in per-tile overhead.
  * **Measured seconds-per-live-pair** (online): a geometry-keyed EWMA
    (:class:`~.feedback.GeometryCostModel`). Because live pairs are
    geometry-invariant, measured rates rank geometries directly;
    candidates the model has measured use their EWMA rate, unmeasured
    ones are bridged through a fitted seconds-per-model-unit scale so
    one measurement anywhere wall-clock-anchors the whole lattice.

Candidates are dropped before scoring when the VMEM working set
(:func:`~...kernels.pair_sim.catalog_vmem_bytes`) of either stage-1
kernel they would run — the compact kernel at its resolved capacity
(:func:`~...kernels.pair_sim.resolve_capacity`), or the mask kernel an
overflow falls back to — exceeds the budget. The lattice is finite and
every geometry is a static kernel arg, so a resident service compiles
at most |lattice| variants during its warmup sweep and then pins the
winner — zero steady-state recompiles (asserted by
benchmarks/tune_bench.py).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import numpy as np

from ...kernels.pair_sim import (GEOMETRY_LATTICE, VMEM_BUDGET_BYTES,
                                 catalog_vmem_bytes, resolve_capacity)
from .feedback import GeometryCostModel
from .ir import MatchJob, TileCatalog
from .lower import lower
from .schedule import tile_costs

__all__ = [
    "GEOMETRY_LATTICE",
    "GeometryScore",
    "TuneReport",
    "catalog_occupancy",
    "autotune",
]

# Static-model coefficients, in dead-cell equivalents: ``beta`` weighs
# per-tile strip DMA traffic (bm+bn rows moved per tile; double
# buffering overlaps it with compute but HBM bandwidth still bounds),
# ``tile_overhead`` the fixed per-grid-step cost (descriptor decode,
# epilogue, DMA issue). Calibrated once against the Fig. 9 sweep in
# benchmarks/tune_bench.py; the online EWMA overrides them as soon as
# real measurements exist.
DEFAULT_BETA = 32.0
DEFAULT_TILE_OVERHEAD = 4096.0


@dataclass(frozen=True)
class GeometryScore:
    """One lattice candidate's exact occupancy + model/measured cost."""
    block_m: int
    block_n: int
    tiles: int              # catalog entries T at this geometry
    cells: int              # T · bm · bn scored MXU cells
    live_pairs: int         # Σ tile_costs — geometry-invariant
    waste: int              # cells − live_pairs (exact dead cells)
    occupancy: float        # live_pairs / cells (0 for empty catalogs)
    model_cost: float       # static roofline, cell-equivalents
    measured_rate: float    # EWMA seconds/live-pair; NaN if unmeasured
    predicted_seconds: float  # NaN when nothing in the lattice is measured

    @property
    def geometry(self) -> Tuple[int, int]:
        return (self.block_m, self.block_n)


@dataclass(frozen=True)
class TuneReport:
    """Autotune outcome: the chosen geometry + the full candidate table
    (sorted best-first) for benchmarks and logs."""
    block_m: int
    block_n: int
    measured: bool          # True when the choice used EWMA wall clock
    scores: Tuple[GeometryScore, ...]

    @property
    def geometry(self) -> Tuple[int, int]:
        return (self.block_m, self.block_n)

    @property
    def best(self) -> GeometryScore:
        return self.scores[0]


def catalog_occupancy(catalog: TileCatalog) -> Tuple[int, int, int]:
    """(cells, live_pairs, waste) of a lowered catalog — exact, from the
    closed-form cost model. ``waste`` equals the number of tile cells
    whose predicate mask is dead (enumerable but never enumerated)."""
    t = catalog.tiles.shape[0]
    cells = t * catalog.block_m * catalog.block_n
    live = int(tile_costs(catalog).sum())
    return cells, live, cells - live


def _score_one(job: MatchJob, bm: int, bn: int, beta: float,
               tile_overhead: float,
               feedback: Optional[GeometryCostModel]) -> GeometryScore:
    catalog = lower(job, bm, bn)
    cells, live, waste = catalog_occupancy(catalog)
    t = catalog.tiles.shape[0]
    model = t * (bm * bn + beta * (bm + bn) + tile_overhead)
    rate = feedback.rate((bm, bn)) if feedback is not None else float("nan")
    return GeometryScore(
        block_m=bm, block_n=bn, tiles=t, cells=cells, live_pairs=live,
        waste=waste, occupancy=(live / cells if cells else 0.0),
        model_cost=model, measured_rate=rate,
        predicted_seconds=float("nan"))


def autotune(job: MatchJob, *,
             lattice: Sequence[Tuple[int, int]] = GEOMETRY_LATTICE,
             d: int = 0, capacity: Optional[int] = None,
             beta: float = DEFAULT_BETA,
             tile_overhead: float = DEFAULT_TILE_OVERHEAD,
             feedback: Optional[GeometryCostModel] = None) -> TuneReport:
    """Choose (block_m, block_n) for ``job`` from ``lattice``.

    ``d`` (feature dim), when given, drops candidates whose compact
    kernel (at ``capacity`` as the executor resolves it) or mask kernel
    would not fit VMEM.
    With a :class:`GeometryCostModel` holding at least one
    measured lattice candidate, ranking is by predicted wall seconds —
    measured candidates at ``rate · live_pairs``, unmeasured ones
    bridged via the fitted seconds-per-model-unit of the measured set.
    Otherwise ranking is by the static model alone.
    """
    cands = []
    for bm, bn in lattice:
        cap = resolve_capacity(bm, bn, capacity)
        if d and max(catalog_vmem_bytes(bm, bn, d, cap),
                     catalog_vmem_bytes(bm, bn, d)) > VMEM_BUDGET_BYTES:
            continue
        cands.append((int(bm), int(bn)))
    if not cands:
        raise ValueError(
            f"no lattice candidate fits VMEM at d={d}, capacity={capacity}")

    scores = [_score_one(job, bm, bn, beta, tile_overhead, feedback)
              for bm, bn in cands]

    # Wall-clock anchor: fit seconds-per-model-unit over measured
    # candidates, project it onto unmeasured ones. live_pairs is the
    # same for every candidate, so measured ranks need no bridging
    # among themselves — the fit only grafts the two populations onto
    # one axis.
    measured = [s for s in scores if not math.isnan(s.measured_rate)]
    use_measured = bool(measured)
    if use_measured:
        kappa = float(np.mean([s.measured_rate * max(s.live_pairs, 1)
                               / s.model_cost for s in measured]))
        scores = [
            GeometryScore(
                **{**s.__dict__,
                   "predicted_seconds":
                       (s.measured_rate * max(s.live_pairs, 1)
                        if not math.isnan(s.measured_rate)
                        else kappa * s.model_cost)})
            for s in scores]
        scores.sort(key=lambda s: s.predicted_seconds)
    else:
        scores.sort(key=lambda s: s.model_cost)

    best = scores[0]
    return TuneReport(block_m=best.block_m, block_n=best.block_n,
                      measured=use_measured, scores=tuple(scores))
