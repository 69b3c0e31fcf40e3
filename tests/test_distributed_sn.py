"""Distributed Sorted Neighborhood: ``run_er`` on a data mesh (subprocess
— the device count must be pinned before jax initializes).

On a mesh, SN runs RepSN: each device scores the band pairs whose
smaller sorted position lies in its row shard and receives only the
w − 1 boundary rows after it, over ⌈(w−1)/n_loc⌉ chained ppermute hops,
never an all-gather. Every leg asserts the same match set as the
one-device ``run_er`` (the four-device legs also the brute-force
``tests/sn_oracle.py``) and the exact bytes each device received:
(w − 1)·d·4 of halo, 0 of gather."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.er import ERConfig, make_products, run_er
    from repro.er.compiler import stage1_stats
    from repro.er.distributed import sn_replication_volume

    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    n_dev = 8
    W, DIM, MAXLEN = 64, 128, 48

    ds = make_products(1024, seed=5)
    n = ds.n - (ds.n % n_dev)          # shard-divisible prefix
    titles = ds.titles[:n]

    def mesh_run(w):
        cfg = ERConfig(strategy="sorted_neighborhood", window=w, r=n_dev,
                       feature_dim=DIM, max_len=MAXLEN)
        before = dict(stage1_stats["interconnect"])
        res = run_er(titles, cfg, mesh=mesh)
        moved = {k: v - before[k]
                 for k, v in stage1_stats["interconnect"].items()}
        return res, moved, run_er(titles, cfg)

    # ---- RepSN on the mesh vs the single-host SN pipeline ----
    res, moved, host = mesh_run(W)
    assert res.matches == host.matches, (len(res.matches),
                                         len(host.matches))
    print("SN dist OK:", len(res.matches), "matches")

    # ---- boundary replication beats all-gather on the wire ----
    halo_bytes, allgather_bytes = sn_replication_volume(n, W, n_dev, DIM)
    assert halo_bytes < allgather_bytes, (halo_bytes, allgather_bytes)
    assert halo_bytes == n_dev * (W - 1) * DIM * 4
    assert moved["halo_bytes"] * n_dev == halo_bytes, moved
    assert moved["flat_bytes"] == 0, moved
    print(f"SN volume OK: halo {halo_bytes} < all-gather {allgather_bytes}")

    # ---- multi-hop: window wider than a shard (w − 1 > n / n_dev) ----
    W2 = n // n_dev + 2
    res2, moved2, host2 = mesh_run(W2)
    assert res2.matches == host2.matches, (len(res2.matches),
                                           len(host2.matches))
    per_hop = sn_replication_volume(n, W2, n_dev, DIM, per_hop=True)
    assert len(per_hop) == 2 and sum(per_hop) == (W2 - 1) * DIM * 4
    assert moved2["halo_bytes"] == sum(per_hop), moved2
    print("SN multi-hop OK:", len(res2.matches), "matches over",
          len(per_hop), "hops")
""")


@pytest.mark.slow
def test_distributed_sn_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for tag in ("SN dist OK", "SN volume OK", "SN multi-hop OK"):
        assert tag in proc.stdout, proc.stdout + proc.stderr


HALO_4DEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    from repro.er import ERConfig, make_products, run_er
    from repro.er.compiler import stage1_stats
    from repro.sharding import make_er_mesh
    from sn_oracle import sn_oracle_matches

    n, w, ties = {n}, {w}, {ties}
    DIM, MAXLEN, n_dev = 128, 48, 4
    titles = list(make_products(n + n // 8, seed=11).titles[:n])
    assert len(titles) == n
    if ties:
        # whole copies and case/space variants: the same sort key on
        # different inputs, in a shuffled record order
        rng = np.random.default_rng(3)
        src = rng.choice(n, size=n // 10, replace=False)
        dst = rng.choice(np.setdiff1d(np.arange(n), src), size=src.size,
                         replace=False)
        for k, (a, b) in enumerate(zip(src, dst)):
            titles[b] = titles[a] if k % 2 else " " + titles[a].upper()
        titles = [titles[i] for i in rng.permutation(n)]
        keys = [t.strip().lower() for t in titles]
        assert len(set(keys)) < n
    cfg = ERConfig(strategy="sorted_neighborhood", window=w, r=16,
                   feature_dim=DIM, max_len=MAXLEN)
    before = dict(stage1_stats["interconnect"])
    res = run_er(titles, cfg, mesh=make_er_mesh(n_dev))
    moved = {{k: v - before[k]
              for k, v in stage1_stats["interconnect"].items()}}
    one = run_er(titles, cfg).matches
    want = sn_oracle_matches(titles, w, feature_dim=DIM, max_len=MAXLEN)
    assert res.matches == one == want, (len(res.matches), len(one),
                                        len(want))
    assert len(want) > 0
    n_loc = -(-n // n_dev)
    hops = -(-(w - 1) // n_loc)
    assert hops == {hops}, hops
    assert moved["halo_bytes"] == (w - 1) * DIM * 4, moved
    assert moved["flat_bytes"] == 0, moved
    s = res.schedule
    assert s["total_cost"] == res.total_pairs, (s, res.total_pairs)
    assert s["device"]["total"] == res.total_pairs
    print("halo OK:", len(want), "matches,", hops, "hop(s)")
""")


@pytest.mark.slow
@pytest.mark.parametrize("n,w,ties,hops", [
    (2048, 100, False, 1),
    (2048, 1200, False, 3),
    (1901, 300, False, 1),
    (1536, 260, True, 1),
], ids=["one-hop", "multi-hop", "n-not-a-multiple-of-512", "tied-keys"])
def test_run_er_sn_halo_4dev(n, w, ties, hops):
    """``run_er`` SN on four devices equals one device and the oracle,
    with exactly w − 1 rows received per device and no gather."""
    env = dict(os.environ)
    here = os.path.dirname(__file__)
    env["PYTHONPATH"] = "src" + os.pathsep + here
    script = HALO_4DEV.format(n=n, w=w, ties=ties, hops=hops)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(here))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "halo OK" in proc.stdout, proc.stdout + proc.stderr
