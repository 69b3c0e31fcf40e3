"""Empty-device-shard regression: under extreme skew the cost-LPT
schedule can place EVERY tile on a few devices and leave others with a
zero-length shard. ``execute(fixed_chunks=False)`` shrinks the chunk to
the largest device shard — this pins that the shrunken chunk still pads
to >= 1 tile (an all-zero tile has an empty validity window, so idle
devices contribute no survivors) and that the mesh run scores exactly
the single-host survivor set. Runs in a subprocess: the simulated device
count must be pinned before jax initializes."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.core import compute_bdm, plan_block_split
    from repro.er.compiler import (execute, lower, plan_to_job,
                                   schedule_tiles, tiles_for_devices)

    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    n_dev = 8

    # Extreme skew: one dominant block, a couple of tiny ones. With
    # block_m = block_n = 64 the whole job lowers to a handful of tiles,
    # so 8-way LPT necessarily leaves devices empty.
    sizes = np.array([120, 5, 3], np.int64)   # sums to 128: 8-shardable
    n = int(sizes.sum())
    bdm = compute_bdm(np.repeat(np.arange(sizes.size), sizes),
                      np.zeros(n, np.int64), sizes.size, 1)
    plan = plan_block_split(bdm, 4)
    cat = lower(plan_to_job(plan), 64, 64)
    sched = schedule_tiles(cat, n_dev=n_dev, policy="cost_lpt")

    tiles_dev = tiles_for_devices(cat, n_dev, schedule=sched)
    per_dev = np.bincount(
        sched.reducer_device[sched.tile_reducer], minlength=n_dev)
    assert (per_dev == 0).any(), per_dev       # the shard IS empty
    assert tiles_dev.shape[1] >= 1             # ... and still pads to >= 1
    print("empty shard present:", int((per_dev == 0).sum()), "devices idle")

    rng = np.random.default_rng(0)
    feats = rng.normal(size=(n, 64)).astype(np.float32)
    feats /= np.linalg.norm(feats, axis=1, keepdims=True)

    want = execute(cat, feats, threshold=0.4)  # single host oracle
    got = execute(cat, feats, threshold=0.4, mesh=mesh, schedule=sched,
                  chunk_tiles=1024, fixed_chunks=False)
    to_set = lambda ab: set(zip(ab[0].tolist(), ab[1].tolist()))
    assert to_set(got) == to_set(want), (len(to_set(got)),
                                         len(to_set(want)))
    assert len(to_set(want)) > 0
    print("empty-shard execute OK:", len(to_set(want)), "survivors")

    # Degenerate end of the same axis: a catalog whose every tile fits
    # ONE device (single tile) — chunk shrinks all the way to 1.
    sizes1 = np.array([40], np.int64)
    n1 = int(sizes1.sum())
    bdm1 = compute_bdm(np.zeros(n1, np.int64), np.zeros(n1, np.int64), 1, 1)
    cat1 = lower(plan_to_job(plan_block_split(bdm1, 1)), 64, 64)
    sched1 = schedule_tiles(cat1, n_dev=n_dev, policy="cost_lpt")
    feats1 = feats[:n1]
    want1 = execute(cat1, feats1, threshold=0.4)
    got1 = execute(cat1, feats1, threshold=0.4, mesh=mesh,
                   schedule=sched1, fixed_chunks=False)
    assert to_set(got1) == to_set(want1)
    print("single-tile catalog OK:", len(to_set(want1)), "survivors")
""")


@pytest.mark.slow
def test_empty_shard_fixed_chunks_false_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for tag in ("empty shard present", "empty-shard execute OK",
                "single-tile catalog OK"):
        assert tag in proc.stdout, proc.stdout + proc.stderr
