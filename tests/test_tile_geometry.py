"""Tile geometry is an execution detail, never a semantics knob: ANY
lattice geometry × {interpret, xla, reference} × {mask, compact} must
reproduce the exact 128×128 match set on Basic / BlockSplit / PairRange
/ SortedNeighborhood catalogs — plus the occupancy model's waste
accounting, the VMEM lowering guard, cost-model state round-trips, the
service warm-start contract, and the mesh-path on-device compaction."""
import numpy as np
import pytest

try:        # hypothesis widens the sweep when present; core parity runs always
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

import jax

from repro.core import (compute_bdm, plan_basic, plan_block_split,
                        plan_pair_range, plan_sorted_neighborhood)
from repro.er import ERService, ServiceConfig, compile_counter, make_products
from repro.er.blocking import exponential_block_ids
from repro.er.compiler import (GEOMETRY_LATTICE, GeometryCostModel,
                               EwmaCostModel, autotune, catalog_occupancy,
                               enumerate_catalog_pairs, execute, lower,
                               plan_to_job, score_catalog, stage1_stats)
from repro.kernels.pair_sim import (VMEM_BUDGET_BYTES, catalog_vmem_bytes,
                                    check_vmem, resolve_capacity)

N, D, R, M = 220, 32, 5, 4
THRESHOLD = 0.3
JOB_NAMES = ("basic", "block_split", "pair_range", "sn")

_rng = np.random.default_rng(17)
_bid = exponential_block_ids(N, b=12, s=1.0, rng=_rng)
_order = np.argsort(_bid, kind="stable")
FEATS = _rng.standard_normal((N, D)).astype(np.float32)
FEATS /= np.linalg.norm(FEATS, axis=1, keepdims=True)
_bid = _bid[_order]
_BDM = compute_bdm(_bid, np.arange(N, dtype=np.int64) % M,
                   int(np.bincount(_bid).shape[0]), M)

_JOBS = {
    "basic": plan_to_job(plan_basic(_BDM, R)),
    "block_split": plan_to_job(plan_block_split(_BDM, R)),
    "pair_range": plan_to_job(plan_pair_range(_BDM, R)),
    "sn": plan_to_job(plan_sorted_neighborhood(N, w=9, r=R)),
}

# Reference leg: brute-force numpy over the enumerated (geometry-free)
# pair set — every scored configuration below must reproduce it exactly.
_COS = FEATS @ FEATS.T


def _ref_matches(name):
    ea, eb = enumerate_catalog_pairs(lower(_JOBS[name], 128, 128))
    keep = _COS[ea, eb] >= THRESHOLD
    return {(min(a, b), max(a, b))
            for a, b in zip(ea[keep].tolist(), eb[keep].tolist())}


_REF = {name: _ref_matches(name) for name in JOB_NAMES}


def _assert_parity(geom, name, impl, compact):
    cat = lower(_JOBS[name], *geom)
    ra, rb = score_catalog(FEATS, cat, threshold=THRESHOLD, impl=impl,
                           compact=compact, chunk_tiles=64)
    got = {(min(a, b), max(a, b)) for a, b in zip(ra.tolist(), rb.tolist())}
    assert got == _REF[name], (geom, name, impl, compact)


@pytest.mark.parametrize("geom", GEOMETRY_LATTICE)
@pytest.mark.parametrize("name", JOB_NAMES)
def test_full_lattice_parity_xla_compact(geom, name):
    """Every lattice geometry × every catalog family on the production
    CPU path (xla twin + on-device compaction)."""
    _assert_parity(geom, name, "xla", compact=True)


@pytest.mark.parametrize("impl,compact",
                         [("xla", False), ("interpret", False),
                          ("interpret", True)])
@pytest.mark.parametrize("geom", [(32, 64), (64, 32), (128, 128)])
def test_parity_mask_and_interpret_paths(geom, impl, compact):
    """Non-square geometries through the dense-mask decode and the
    interpret-mode kernel emulator (which ignores ``compact``)."""
    _assert_parity(geom, "block_split", impl, compact)


if HAVE_HYPOTHESIS:
    @given(geom=st.sampled_from(GEOMETRY_LATTICE),
           name=st.sampled_from(JOB_NAMES),
           impl=st.sampled_from(("interpret", "xla")),
           compact=st.booleans())
    @settings(max_examples=24, deadline=None)
    def test_any_geometry_reproduces_the_128x128_match_set(
            geom, name, impl, compact):
        _assert_parity(geom, name, impl, compact)


@pytest.mark.parametrize("geom", [(32, 32), (64, 32), (128, 128), (32, 256)])
@pytest.mark.parametrize("name", JOB_NAMES)
def test_occupancy_waste_equals_enumerated_dead_cells(geom, name):
    """The static model's waste is EXACT: cells − Σ tile_costs equals the
    cells not covered by any enumerated live pair, and the live-pair sum
    is geometry-invariant (the plan's own pair total)."""
    job = _JOBS[name]
    cat = lower(job, *geom)
    cells, live, waste = catalog_occupancy(cat)
    ea, _ = enumerate_catalog_pairs(cat)
    assert cells == cat.tiles.shape[0] * geom[0] * geom[1]
    assert live == ea.size == job.total_pairs
    assert waste == cells - ea.size


def test_every_lattice_candidate_fits_vmem_double_buffered():
    """Mask path and compact path at the resolved default capacity fit
    the budget for every lattice candidate at d=256; one slot per cell
    on the largest tiles legitimately does not (the lowering guard
    catches it)."""
    for bm, bn in GEOMETRY_LATTICE:
        assert catalog_vmem_bytes(bm, bn, 256) <= VMEM_BUDGET_BYTES, (bm, bn)
        check_vmem(bm, bn, 256, capacity=resolve_capacity(bm, bn))
    assert catalog_vmem_bytes(256, 256, 256, capacity=256 * 256) \
        > VMEM_BUDGET_BYTES


def test_check_vmem_rejects_oversized_working_set():
    with pytest.raises(ValueError, match="VMEM"):
        check_vmem(1024, 1024, 4096)


@pytest.mark.parametrize("geom,asked,resolved", [
    ((128, 128), None, 1024), ((16, 16), None, 256),
    ((32, 32), 12_288, 1024), ((64, 64), 64, 64)])
def test_capacity_resolves_within_the_tile(geom, asked, resolved):
    """The executor, the mesh scorer and autotune all run the compact
    kernel at resolve_capacity: the default (or asked) slots, never more
    than the tile's cells — check_vmem refuses more, outside its fit."""
    assert resolve_capacity(*geom, asked) == resolved
    check_vmem(*geom, 256, capacity=resolved)
    with pytest.raises(ValueError, match="cells"):
        check_vmem(*geom, 256, capacity=geom[0] * geom[1] + 1)


def test_autotune_keeps_only_geometries_both_kernels_fit():
    """autotune judges the capacity the kernel will run with: at the
    default every lattice entry fits at d=256, and a d that only the mask
    kernel fits drops the candidate all the same."""
    job = _JOBS["block_split"]
    rep = autotune(job, d=256)
    assert {s.geometry for s in rep.scores} == set(GEOMETRY_LATTICE)
    d = 1_400   # 256×256: mask fits, compact at 1,024 slots does not
    assert catalog_vmem_bytes(256, 256, d) <= VMEM_BUDGET_BYTES \
        < catalog_vmem_bytes(256, 256, d, resolve_capacity(256, 256))
    assert (256, 256) not in {s.geometry for s in autotune(job, d=d).scores}


def test_autotune_raises_when_nothing_fits():
    with pytest.raises(ValueError):
        autotune(_JOBS["block_split"], d=100_000)


def test_autotune_prefers_occupancy_on_skew():
    """At s=1.0 skew the fixed 128×128 tile is mostly dead cells — the
    static pick must beat it on occupancy AND model cost."""
    rep = autotune(_JOBS["block_split"], d=D)
    assert rep.geometry != (128, 128)
    by_geom = {s.geometry: s for s in rep.scores}
    best, base = by_geom[rep.geometry], by_geom[(128, 128)]
    assert best.occupancy > base.occupancy
    assert best.model_cost < base.model_cost
    assert best.live_pairs == base.live_pairs  # geometry-invariant


def test_autotune_feedback_overrides_static_ranking():
    """One measured rate anywhere wall-clock-anchors the lattice; a
    measured-fast geometry must win over the static favourite."""
    job = _JOBS["block_split"]
    static = autotune(job, d=D)
    loser = next(s for s in static.scores if s.geometry != static.geometry)
    fb = GeometryCostModel()
    fb.observe(static.geometry, 1e6, 10.0)   # static pick measured slow
    fb.observe(loser.geometry, 1e6, 0.1)     # runner-up measured fast
    refit = autotune(job, d=D, feedback=fb)
    assert refit.geometry == loser.geometry
    assert refit.measured


def test_geometry_cost_model_state_roundtrip():
    fb = GeometryCostModel()
    fb.observe((64, 64), 1e6, 0.5)
    fb.observe((32, 32), 2e6, 0.4)
    clone = GeometryCostModel.from_state(fb.to_state())
    for g in ((64, 64), (32, 32)):
        assert clone.rate(g) == fb.rate(g)
    assert clone.best() == fb.best() == (32, 32)
    assert np.isnan(clone.rate((256, 256)))
    with pytest.raises(ValueError):
        GeometryCostModel.from_state({"version": 99})


def test_ewma_cost_model_state_roundtrip():
    m = EwmaCostModel(n_dev=3)
    rng = np.random.default_rng(0)
    from repro.er.compiler.feedback import N_TILE_CLASSES
    for dev in range(3):
        m.observe(dev, rng.uniform(1, 9, N_TILE_CLASSES), rng.uniform(.1, 2))
    clone = EwmaCostModel.from_state(m.to_state())
    for dev in range(3):
        assert clone.rate(dev) == pytest.approx(m.rate(dev), nan_ok=True)
        for c in range(N_TILE_CLASSES):
            assert clone.rate(dev, c) == pytest.approx(m.rate(dev, c),
                                                       nan_ok=True)
    assert clone.observations == m.observations
    with pytest.raises(ValueError):
        EwmaCostModel.from_state({"version": 0})


def _service_cfg():
    return ServiceConfig(feature_dim=64, max_len=48, r=8, m=4,
                         query_buckets=(8,), tile_chunk=64,
                         autotune_tiles=True,
                         autotune_lattice=((32, 32), (64, 64)))


def test_service_warm_start_skips_sweep():
    """A service seeded with an exported feedback state skips the warmup
    geometry sweep: fewer compiles, same pinned geometry, and it serves
    the exact same answers as the cold service."""
    titles = make_products(300, seed=3).titles
    cold = ERService(titles, _service_cfg())
    with compile_counter() as cc_cold:
        cold.warmup()
    state = cold.export_feedback_state()
    assert cold.tune_report is not None
    assert state["geometry"]["rates"], "sweep left no measured rates"

    cfg = _service_cfg()
    cfg.feedback_state = state
    warm = ERService(titles, cfg)
    assert warm.geometry_feedback.best(cfg.autotune_lattice) is not None
    with compile_counter() as cc_warm:
        warm.warmup()
    assert warm.tile_geometry == cold.tile_geometry
    assert cc_warm.count < cc_cold.count, \
        (cc_warm.count, cc_cold.count)
    # and the warm service serves the same answers
    qs = titles[:8]
    assert set(warm.match(qs)) == set(cold.match(qs))


def test_mesh_compact_path_decodes_on_device():
    """The mesh execution path decodes stage-1 survivors from the packed
    epilogue — compact_decodes increments, nonzero_decodes does not."""
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    cat = lower(_JOBS["block_split"], 64, 64)
    before = dict(stage1_stats)
    ra, rb = execute(cat, FEATS, threshold=THRESHOLD, impl="xla",
                     mesh=mesh, chunk_tiles=64)
    got = {(min(a, b), max(a, b)) for a, b in zip(ra.tolist(), rb.tolist())}
    assert got == _REF["block_split"]
    assert stage1_stats["compact_decodes"] > before["compact_decodes"]
    assert stage1_stats["nonzero_decodes"] == before["nonzero_decodes"]
