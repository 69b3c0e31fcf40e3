"""Distributed ER runtime on 8 simulated devices (subprocess: the device
count must be pinned before jax initializes, and the main test session
runs single-device)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np, jax.numpy as jnp
    from repro.core import compute_bdm, entity_indices, blocked_layout, plan_pair_range, plan_basic
    from repro.er.blocking import prefix_block_ids
    from repro.er.datasets import make_products
    from repro.er.encode import encode_titles, ngram_features
    from repro.er.distributed import (compute_bdm_sharded, match_catalog_dist,
                                      match_pair_range_dist,
                                      match_shards_hostplan, plan_rows_for_devices,
                                      device_assignment)
    from repro.er.executor import build_catalog, verify_pairs
    from repro.er.pipeline import run_er, ERConfig

    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    n_dev = 8

    ds = make_products(1024, seed=5)
    bid, _ = prefix_block_ids(ds.titles, ds.prefix_len)
    n = ds.n - (ds.n % n_dev)      # shard-divisible prefix
    titles = ds.titles[:n]
    bid = bid[:n]
    num_blocks = int(bid.max()) + 1

    # ---- Job 1 on the mesh equals the host BDM ----
    part = np.repeat(np.arange(n_dev), n // n_dev)
    bdm_host = compute_bdm(bid, part, num_blocks, n_dev)
    bdm_mesh = np.asarray(compute_bdm_sharded(
        jnp.asarray(bid, jnp.int32), num_blocks, mesh))
    np.testing.assert_array_equal(bdm_host, bdm_mesh)
    print("BDM OK")

    # ---- Job 2 (PairRange, fully in-jit) equals the host pipeline ----
    codes, lens = encode_titles(titles, 48)
    feats = ngram_features(codes, dim=128, lengths=lens)
    eidx = entity_indices(bid, part, bdm_host)
    plan = plan_pair_range(bdm_host, n_dev)
    perm, estart = blocked_layout(bid, eidx, plan.block_sizes)
    fb = jnp.asarray(feats[perm]); cb = jnp.asarray(codes[perm]); lb = jnp.asarray(lens[perm])
    ra, rb, mask, score = match_pair_range_dist(fb, cb, lb, plan, mesh)
    got = set()
    ra, rb, mask = np.asarray(ra), np.asarray(rb), np.asarray(mask)
    for d in range(n_dev):
        for a, b, m in zip(ra[d], rb[d], mask[d]):
            if m:
                ga, gb = int(perm[a]), int(perm[b])
                got.add((min(ga, gb), max(ga, gb)))
    res = run_er(titles, ERConfig(strategy="pair_range", r=n_dev, m=n_dev,
                                  feature_dim=128, max_len=48,
                                  match_missing_keys=False))
    assert got == res.matches, (len(got), len(res.matches))
    print("PairRange dist OK:", len(got), "matches")

    # ---- hostplan executor (Basic) finds the same matches ----
    bplan = plan_basic(bdm_host, n_dev)
    rows = [(np.zeros(0, np.int64), np.zeros(0, np.int64)) for _ in range(n_dev)]
    sizes = plan.block_sizes
    for k_blk in range(num_blocks):
        if sizes[k_blk] < 2: continue
        x, y = np.triu_indices(int(sizes[k_blk]), k=1)
        r = int(bplan.block_reducer[k_blk])
        pa, pb = rows[r]
        rows[r] = (np.concatenate([pa, estart[k_blk] + x]),
                   np.concatenate([pb, estart[k_blk] + y]))
    rows_a, rows_b, valid = plan_rows_for_devices(rows, n_dev, n_dev)
    mask2, _ = match_shards_hostplan(fb, cb, lb,
                                     jnp.asarray(rows_a), jnp.asarray(rows_b),
                                     jnp.asarray(valid), mesh)
    got2 = set()
    mask2 = np.asarray(mask2)
    for d in range(n_dev):
        for a, b, m in zip(rows_a[d], rows_b[d], mask2[d]):
            if m:
                ga, gb = int(perm[a]), int(perm[b])
                got2.add((min(ga, gb), max(ga, gb)))
    assert got2 == res.matches
    print("hostplan dist OK")

    # ---- tile-catalog executor on the mesh (Basic + BlockSplit + PairRange,
    # stage 1 per-device tile shards, stage 2 host verify) ----
    from repro.core import plan_block_split
    for mk_plan in (lambda: bplan, lambda: plan_block_split(bdm_host, n_dev),
                    lambda: plan):
        cplan = mk_plan()
        cat = build_catalog(cplan, block_m=128, block_n=128)
        ca, cb = match_catalog_dist(fb, cat, mesh, threshold=0.8 - 0.25)
        ha, hb = verify_pairs(codes[perm], lens[perm], codes[perm], lens[perm],
                              ca, cb, 0.8)
        got3 = set()
        for a, b in zip(ha, hb):
            ga, gb = int(perm[a]), int(perm[b])
            got3.add((min(ga, gb), max(ga, gb)))
        assert got3 == res.matches, (type(cplan).__name__, len(got3), len(res.matches))
    print("catalog dist OK")

    # ---- elasticity: reducers respread over healthy devices ----
    healthy = np.ones(n_dev, bool); healthy[[2, 5]] = False
    assign = device_assignment(32, n_dev, healthy)
    assert set(assign) == set(np.flatnonzero(healthy))
    counts = np.bincount(assign, minlength=n_dev)
    assert counts[2] == 0 and counts[5] == 0
    assert counts[healthy].max() - counts[healthy].min() <= 1
    print("elastic reassignment OK")
""")


@pytest.mark.slow
def test_distributed_er_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for tag in ("BDM OK", "PairRange dist OK", "hostplan dist OK",
                "elastic reassignment OK"):
        assert tag in proc.stdout, proc.stdout + proc.stderr
