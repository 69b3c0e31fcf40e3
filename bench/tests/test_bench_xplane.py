"""The trace reduction and the work counts behind the per-layer metrics."""
from __future__ import annotations

import pytest

import benchkit  # noqa: F401  (puts bench/ and src/ on the path)
import xplane
from xplane import Event, Trace
import work


def synthetic() -> Trace:
    ops = {0: [Event("fusion.1", 1.0, 2.0), Event("catalog_kernel", 1.5, 3.0),
               Event("catalog_kernel", 6.0, 7.0)],
           1: [Event("all-gather.2", 0.5, 1.5), Event("catalog_kernel", 9.0,
                                                      11.0)]}
    spans = [Event("bench.window", 0.0, 10.0), Event("bench.job", 0.2, 5.0),
             Event("bench.job", 5.0, 9.9)]
    return Trace(devices=ops, spans=spans, t0=0.0, t1=10.0)


def test_busy_is_the_union_of_ops_inside_the_window():
    tr = synthetic()
    assert xplane.busy_s(tr, 0) == pytest.approx(3.0)      # [1,3] + [6,7]
    assert xplane.busy_s(tr, 1) == pytest.approx(2.0)      # clipped at 10
    assert xplane.op_seconds(tr, 0, "catalog") == pytest.approx(2.5)
    assert xplane.op_seconds(tr, 1, "all-gather") == pytest.approx(1.0)
    assert xplane.op_count(tr, 1, "catalog") == 1


def test_idle_gaps_are_named_by_the_open_span():
    gaps = xplane.idle_gaps(synthetic())
    # device union: [0.5,3] [6,7] [9,10]; gaps [0,0.5] [3,6] [7,9]
    assert gaps[0] == ("bench.job", pytest.approx(3.0))
    assert gaps[1] == ("bench.job", pytest.approx(2.0))
    assert gaps[2] == ("bench.job", pytest.approx(0.5))
    assert sum(g for _, g in gaps) == pytest.approx(5.5)


def test_top_ops_sum_over_devices():
    top = dict(xplane.top_ops(synthetic()))
    assert top["catalog_kernel"] == pytest.approx(3.5)


def test_merge():
    assert xplane.merge([(3, 4), (0, 1), (0.5, 2), (4, 5)]) == [(0, 2), (3, 5)]


def test_stage1_work_ignores_the_tile_geometry():
    """The roofline's count is the plan's: the same for 128x128 and
    256x128 catalogs of one job, which tile it differently."""
    import numpy as np
    from corpus import build_corpus
    from repro.er.blocking import prefix_block_ids
    from repro.core import compute_bdm, plan_pair_range
    from repro.er.compiler import lower, plan_to_job, tile_costs
    c = build_corpus(dict(n_records=3000, head_frac=0.018, pair_share=0.71,
                          dup_frac=0.05), 1)
    bid, _ = prefix_block_ids(c.titles, c.prefix_len)
    part = np.minimum(np.arange(c.n) * 20 // c.n, 19)
    plan = plan_pair_range(compute_bdm(bid, part, int(bid.max()) + 1, 20),
                           100)
    job = plan_to_job(plan)
    small, tall = lower(job, 128, 128), lower(job, 256, 128)
    assert small.num_tiles != tall.num_tiles
    for cat in (small, tall):
        assert int(np.sum(tile_costs(cat))) == plan.total_pairs
    ops, nbytes = work.stage1_work(plan.total_pairs, c.n, 256)
    assert ops == 2 * plan.total_pairs * 256 and nbytes == 4 * c.n * 256


def test_peaks_are_keyed_by_device_kind():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    # the bound: 2*10^9 ops need 10.15 us at peak, 4 MB need 4.9 us
    assert work.roofline_percent(2e9, 4e6, 20.3e-6, v5e) == pytest.approx(
        100 * (2e9 / 197e12) / 20.3e-6)
    assert work.roofline_percent(2e9, 4e6, 20.3e-6, v5e, chips=4) \
        == pytest.approx(25 * (2e9 / 197e12) / 20.3e-6)


def test_chip_trace_totals():
    """A trace recorded on a TPU v5e: two run_er jobs over 3,000 titles
    (1,032 tiles: one 1,024-tile and one 8-tile stage-1 launch, and one
    stage-2 chunk, per job). The totals were read from the raw events
    with ``jax.profiler.ProfileData`` when the trace was recorded."""
    import math
    tr = xplane.load(str(benchkit.BENCH / "tests" / "data"
                         / "run_er_3000.xplane.pb.gz"))
    assert sorted(tr.devices) == [0]
    assert tr.window_s == pytest.approx(0.560550, abs=1e-6)
    kernel = xplane.op_seconds(tr, 0, r"^%pair_scores_catalog")
    assert kernel == pytest.approx(0.050613, abs=1e-6)
    assert xplane.op_count(tr, 0, r"^%pair_scores_catalog") == 4
    edit = xplane.op_seconds(tr, 0, r"^jit_edit_distance\(", modules=True)
    assert edit == pytest.approx(0.004599, abs=1e-6)
    busy = xplane.busy_s(tr, 0)
    assert busy == pytest.approx(0.055340, abs=1e-6)
    assert kernel + 0.0044 < busy < tr.window_s
    gaps = xplane.idle_gaps(tr)
    assert {name for name, _ in gaps} == {"bench.job"}
    assert math.isclose(sum(g for _, g in xplane.idle_gaps(tr, 10 ** 6)),
                        tr.window_s - busy, rel_tol=1e-9)
