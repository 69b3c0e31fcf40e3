"""Sharded-index ER service on 8 simulated devices (subprocess: the
device count must be pinned before jax initializes). Asserts the
acceptance contract end to end: streaming ≡ batch exact match-set
equality AND zero steady-state recompiles on the 8-device path, plus the
reducer → device routing invariant of the tile shards."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.er import (ERConfig, ERService, ServiceConfig, compile_counter,
                          cross_restrict, make_products, run_er)
    from repro.er.distributed import (device_assignment, match_catalog_2src_dist,
                                      plan_tiles_for_devices)
    from repro.er.executor import RED, catalog_for_two_source, verify_pairs

    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    ds = make_products(600, seed=5)
    corpus = ds.titles[:500] + [""]
    queries = ds.titles[500:560] + ["", "@@@ fresh block"]
    cfg = ServiceConfig(feature_dim=128, max_len=48, r=16, m=8,
                        query_buckets=(8, 32, 64), tile_chunk=64)
    svc = ERService(corpus, cfg, mesh=mesh)
    svc.warmup()

    # ---- streaming == batch over the sharded index ----
    got, off = set(), 0
    for sz in (9, 33, 13, 7):
        for a, b in svc.match(queries[off:off+sz]):
            got.add((a, b + off))
        off += sz
    assert off == len(queries)
    oracle = run_er(corpus + queries,
                    ERConfig(feature_dim=128, max_len=48, r=16, m=8))
    want = cross_restrict(oracle.matches, len(corpus))
    assert got == want, (len(got), len(want))
    print("sharded stream==batch OK:", len(got), "matches")

    # ---- zero steady-state recompiles on the mesh ----
    rng = np.random.default_rng(0)
    with compile_counter() as steady:
        for _ in range(20):
            sz = int(rng.integers(1, 65))
            svc.match([queries[int(rng.integers(0, len(queries)))]
                       for _ in range(sz)])
    assert steady.count == 0, steady.count
    print("sharded zero-recompile OK")

    # ---- tile shards route reducer -> device round-robin ----
    from repro.core import compute_bdm
    from repro.core.two_source import TwoSourceBDM, plan_pair_range_2src
    qb = np.asarray([0, 0, 1, 2] * 4)
    bdm2 = TwoSourceBDM(
        bdm_r=compute_bdm(np.arange(16) % 3, np.zeros(16, np.int64), 3, 1),
        bdm_s=compute_bdm(qb, np.zeros_like(qb), 3, 1))
    plan = plan_pair_range_2src(bdm2, 16)
    cat = catalog_for_two_source(plan, 16, 16)
    tiles_dev = plan_tiles_for_devices(cat, 8)
    dev_of = device_assignment(16, 8)
    for d in range(8):
        mine = tiles_dev[d]
        live = mine[mine[:, 3] > 0]          # R1 > 0: real entries
        assert all(dev_of[red] == d for red in live[:, RED].tolist())
    print("reducer routing OK")

    # ---- one-shot match_catalog_2src_dist == host cosine oracle ----
    from repro.er.executor import catalog_for_cross
    from repro.er.pipeline import featurize
    from jax.sharding import NamedSharding, PartitionSpec as P
    _, _, cf = featurize(corpus[:64], cfg)     # 64 rows: 8 per device
    _, _, qf = featurize(queries[:16], cfg)
    cf_sharded = jax.device_put(cf, NamedSharding(mesh, P("data")))
    cross = catalog_for_cross(64, 16, r=16, block_m=16, block_n=16)
    ca, cb = match_catalog_2src_dist(cf_sharded, qf, cross, mesh,
                                     threshold=0.55, chunk_tiles=32)
    wa, wb = np.nonzero(cf @ qf.T >= 0.55)
    assert set(zip(ca.tolist(), cb.tolist())) == \
        set(zip(wa.tolist(), wb.tolist()))
    print("one-shot 2src dist OK:", ca.size, "survivors")
""")


@pytest.mark.slow
def test_distributed_service_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for tag in ("sharded stream==batch OK", "sharded zero-recompile OK",
                "reducer routing OK", "one-shot 2src dist OK"):
        assert tag in proc.stdout, proc.stdout + proc.stderr
