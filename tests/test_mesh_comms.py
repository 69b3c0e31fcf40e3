"""2-D mesh comms policies: planner unit tests + exact-parity sweeps.

Host-side tests cover the pure planner (`compiler.comms`): hop/byte
accounting, locality placement, alignment gates and pinned-hop
fallbacks, and the buffer-local tile rewrite.

Subprocess tests (the device count must be pinned before jax
initializes) assert the load-bearing contract: the ring and
hierarchical strip exchanges, with and without a model axis, produce
EXACTLY the flat all-gather's stage-1 survivor set — on non-square
device grids, under both the mask and compact epilogues — and the
multi-hop halo executor reproduces the brute-force SN oracle at
windows wider than a shard (w − 1 > n / n_dev)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.er.compiler import (CommsPlan, comms_volume, default_group,
                               halo_bytes_per_device, halo_hop_rows, lower,
                               plan_comms, plan_to_job,
                               psum_bytes_per_device, rewrite_tiles_local)
from repro.er.compiler.ir import A_TILE, R0, R1, NCOLS
from repro.core import compute_bdm, plan_pair_range


def _blocked_catalog(n=1024, n_blocks=16, r=8, bm=64, bn=64, seed=0):
    """A realistic blocked self-join catalog: contiguous same-size-ish
    blocks lowered through the production pair_range planner."""
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.choice(np.arange(1, n), n_blocks - 1, replace=False))
    sizes = np.diff(np.concatenate([[0], cuts, [n]]))
    bids = np.repeat(np.arange(n_blocks), sizes)
    bdm = compute_bdm(bids, np.zeros(n, np.int64), n_blocks, 1)
    return lower(plan_to_job(plan_pair_range(bdm, r)), bm, bn)


# ---------------------------------------------------------------------------
# Planner units
# ---------------------------------------------------------------------------

def test_default_group():
    assert default_group(16) == 4
    assert default_group(8) == 2
    assert default_group(7) == 1
    assert default_group(1) == 1
    assert default_group(64) == 8


def test_halo_hop_rows_and_bytes():
    assert halo_hop_rows(128, 300) == [128, 128, 44]
    assert halo_hop_rows(128, 128) == [128]
    assert halo_hop_rows(128, 5) == [5]
    assert halo_hop_rows(128, 0) == []
    # bytes received = exactly halo rows, regardless of hop count
    assert sum(halo_bytes_per_device(128, 300, 64)) == 300 * 64 * 4


def test_psum_bytes():
    # ring-reduce over the model axis: 2·(m−1)/m of the payload
    payload = 10 * 64 * 64 * 4
    assert psum_bytes_per_device(1, 10, 64, 64) == 0
    assert psum_bytes_per_device(2, 10, 64, 64) == payload
    assert psum_bytes_per_device(4, 10, 64, 64) == 2 * 3 * payload // 4


def test_ring_plan_locality_beats_flat():
    cat = _blocked_catalog()
    plan = plan_comms(cat, 1024, 8, policy="ring", feature_dim=64)
    assert plan.policy == "ring" and plan.fallback is None
    assert 0 < plan.hops < 7          # blocked locality < full exchange
    assert plan.device_of_tile.shape == (cat.num_tiles,)
    vol = plan.bytes_received_per_device()
    flat = plan_comms(cat, 1024, 8, policy="flat", feature_dim=64)
    assert vol["total"] < flat.bytes_received_per_device()["total"]


def test_hierarchical_plan_shape():
    cat = _blocked_catalog()
    plan = plan_comms(cat, 1024, 8, policy="hierarchical", feature_dim=64)
    assert plan.policy == "hierarchical" and plan.group == 2
    vol = plan.bytes_received_per_device()
    assert vol["hier_intra"] == (plan.group - 1) * plan.n_loc * 64 * 4
    # base is group-panel-aligned, one origin per device
    assert plan.base.shape == (8,)
    assert all(b % (plan.group * plan.n_loc) == 0 for b in plan.base)


def test_alignment_gates_degrade_to_flat():
    cat = _blocked_catalog()
    # rows not shard-divisible
    p = plan_comms(cat, 1001, 8, policy="ring", feature_dim=64)
    assert p.policy == "flat" and "divisible" in p.fallback
    # n_loc not a tile-geometry multiple (n_loc=96, bm=64)
    p = plan_comms(cat, 768, 8, policy="ring", feature_dim=64)
    assert p.policy == "flat" and p.fallback is not None


def test_pinned_hops():
    cat = _blocked_catalog()
    need = plan_comms(cat, 1024, 8, policy="ring", feature_dim=64).hops
    # pin below the need → degrade, never a recompile-shaped surprise
    p = plan_comms(cat, 1024, 8, policy="ring", feature_dim=64,
                   pin_hops=need - 1)
    assert p.policy == "flat" and "pinned" in p.fallback
    # pin above the need → over-gather at the pinned count (exact)
    p = plan_comms(cat, 1024, 8, policy="ring", feature_dim=64,
                   pin_hops=need + 2)
    assert p.policy == "ring" and p.hops == need + 2


def test_unknown_policy_raises():
    cat = _blocked_catalog()
    with pytest.raises(ValueError):
        plan_comms(cat, 1024, 8, policy="mesh2d", feature_dim=64)


def test_rewrite_tiles_local():
    tiles = np.zeros((2, 3, NCOLS), np.int32)
    tiles[0, 0, [A_TILE, R0, R1]] = [2, 128, 192]   # live, device 0
    tiles[1, 0, [A_TILE, R0, R1]] = [8, 512, 576]   # live, device 1
    base = np.array([128, 512])
    out = rewrite_tiles_local(tiles, base, 64, 64, shift_b=False)
    assert out[0, 0, A_TILE] == 0 and out[0, 0, R0] == 0
    assert out[1, 0, A_TILE] == 0 and out[1, 0, R0] == 0
    assert (out[0, 1] == 0).all()                   # dead tiles untouched
    with pytest.raises(ValueError):
        rewrite_tiles_local(tiles, np.array([100, 512]), 64, 64)


def test_comms_volume_scaling_64_dev():
    """The fig13 model: ring/hierarchical bytes-received per device drop
    from the all-gather's O(n) to O(n/n_dev · hops)."""
    cat = _blocked_catalog(n=4096, n_blocks=64)
    for n_dev in (16, 64):
        v = comms_volume(cat, 4096, n_dev, feature_dim=64)
        assert v["ring"] < v["flat_gather"]
        hier = v["hier_intra"] + v["hier_inter"]
        assert hier < v["flat_gather"]
        assert v["ring"] == v["ring_hops"] * (4096 // n_dev) * 64 * 4


def test_plan_summary_round_trips():
    cat = _blocked_catalog()
    plan = plan_comms(cat, 1024, 8, policy="ring", feature_dim=64)
    s = plan.summary()
    assert s["policy"] == "ring" and s["hops"] == plan.hops
    assert s["bytes_received_per_device"]["total"] > 0
    assert isinstance(plan, CommsPlan)


# ---------------------------------------------------------------------------
# Exact parity on simulated device grids (subprocess)
# ---------------------------------------------------------------------------

PARITY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax, numpy as np, jax.numpy as jnp
    from repro.sharding import make_er_mesh
    from repro.core import compute_bdm, plan_pair_range
    from repro.er.compiler import execute, lower, plan_comms, plan_to_job
    from repro.er.compiler.execute import stage1_stats

    N_DATA, N_MODEL = {n_data}, {n_model}
    BM = BN = 64
    n = N_DATA * 128                       # n_loc = 128, BM | n_loc
    d = 64
    rng = np.random.default_rng(7)
    cuts = np.sort(rng.choice(np.arange(1, n), 15, replace=False))
    bids = np.repeat(np.arange(16), np.diff(np.r_[0, cuts, n]))
    bdm = compute_bdm(bids, np.zeros(n, np.int64), 16, 1)
    cat = lower(plan_to_job(plan_pair_range(bdm, 8)), BM, BN)
    feats = rng.standard_normal((n, d)).astype(np.float32)
    feats /= np.linalg.norm(feats, axis=1, keepdims=True)

    mesh = make_er_mesh(N_DATA, N_MODEL)
    model_axis = "model" if N_MODEL > 1 else None

    def survivors(comms, compact, use_mesh=True):
        ra, rb = execute(cat, jnp.asarray(feats), threshold=0.1,
                         impl="xla", mesh=mesh if use_mesh else None,
                         comms=comms, compact=compact,
                         model_axis=model_axis if use_mesh else None)
        return set(zip(ra.tolist(), rb.tolist()))

    ref = survivors("flat", True, use_mesh=False)   # single-host oracle
    assert ref, "degenerate test: no stage-1 survivors"
    for comms in ("flat", "ring", "hierarchical"):
        plan = plan_comms(cat, n, N_DATA, policy=comms, n_model=N_MODEL,
                          feature_dim=d, self_join=True)
        assert plan.fallback is None, (comms, plan.fallback)
        expect = plan.bytes_received_per_device()
        before = dict(stage1_stats["interconnect"])
        for compact in (True, False):
            got = survivors(comms, compact)
            assert got == ref, (comms, compact, len(got), len(ref))
        after = stage1_stats["interconnect"]
        # counters move exactly when the plan predicts traffic
        if comms == "ring":
            moved = after["ring_bytes"] > before["ring_bytes"]
            assert moved == (expect.get("ring", 0) > 0), (expect, after)
        if comms == "hierarchical":
            moved = (after["hier_intra_bytes"] + after["hier_inter_bytes"]
                     > before["hier_intra_bytes"] + before["hier_inter_bytes"])
            assert moved == (expect.get("total", 0) > 0), (expect, after)
        if N_MODEL > 1:
            assert after["psum_bytes"] > before["psum_bytes"]
    print("parity OK:", len(ref), "survivors on", N_DATA, "x", N_MODEL)
""")


@pytest.mark.slow
@pytest.mark.parametrize("n_data,n_model", [(2, 1), (4, 1), (8, 1),
                                            (8, 2), (16, 1)])
def test_comms_policy_parity(n_data, n_model):
    """Flat vs ring vs hierarchical — exact stage-1 survivor-set
    equality, mask AND compact epilogues, including a non-square (8, 2)
    data×model grid. The model-axis case uses a margin-safe threshold:
    the psum reassociates the d-dot, so only scores within ulps of the
    threshold itself could flip (see make_scorer's contract)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    script = PARITY.format(n_dev=n_data * n_model, n_data=n_data,
                           n_model=n_model)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "parity OK" in proc.stdout, proc.stdout + proc.stderr


MULTI_HOP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax, numpy as np
    from repro.er import ERConfig, make_products, run_er
    from repro.er.compiler import stage1_stats
    from repro.er.distributed import sn_replication_volume
    from repro.sharding import make_er_mesh
    from sn_oracle import sn_oracle_matches

    n_dev, DIM, MAXLEN = 8, 128, 48
    ds = make_products(512, seed=9)
    n = ds.n - (ds.n % n_dev)
    titles = ds.titles[:n]
    W = n // n_dev + 37                    # w − 1 > n / n_dev: 2 hops

    before = stage1_stats["interconnect"]["halo_bytes"]
    res = run_er(titles, ERConfig(strategy="sorted_neighborhood", window=W,
                                  r=n_dev, feature_dim=DIM, max_len=MAXLEN),
                 mesh=make_er_mesh(n_dev))
    got = res.matches
    want = sn_oracle_matches(titles, W, feature_dim=DIM, max_len=MAXLEN)
    assert got == want, (len(got), len(want))
    hops = -(-(W - 1) // (n // n_dev))
    assert hops >= 2, hops
    per_hop = sn_replication_volume(n, W, n_dev, DIM, per_hop=True)
    assert len(per_hop) == hops
    assert sum(per_hop) == (W - 1) * DIM * 4
    assert stage1_stats["interconnect"]["halo_bytes"] - before \
        == sum(per_hop)
    print("multi-hop oracle OK:", len(got), "matches,", hops, "hops")
""")


@pytest.mark.slow
def test_multi_hop_halo_vs_oracle():
    """RepSN at a window wider than a shard: the chained-hop halo
    exchange must reproduce the brute-force SN oracle exactly, and the
    per-hop byte schedule must sum to precisely (w − 1) rows."""
    env = dict(os.environ)
    here = os.path.dirname(__file__)
    env["PYTHONPATH"] = "src" + os.pathsep + here
    proc = subprocess.run([sys.executable, "-c", MULTI_HOP], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(here))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "multi-hop oracle OK" in proc.stdout, proc.stdout + proc.stderr
