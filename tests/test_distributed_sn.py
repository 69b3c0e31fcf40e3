"""Distributed Sorted Neighborhood on 8 simulated devices (subprocess —
the device count must be pinned before jax initializes).

Regression: the RepSN boundary-replication path (w−1 halo rows chained
between adjacent shards via ppermute, no all-gather) produces the same
match set as the single-host ``run_er`` SN pipeline, and the replicated
byte volume is strictly below the full all-gather volume. Windows wider
than a shard (w − 1 > n / n_dev) chain ⌈(w−1)/n_loc⌉ hops instead of
raising — the multi-hop leg asserts equality there too, plus the
per-hop byte schedule."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np, jax.numpy as jnp
    from repro.er import ERConfig, make_products, run_er, sn_sort_order
    from repro.er.encode import encode_titles, ngram_features
    from repro.er.distributed import match_sn_dist, sn_replication_volume
    from repro.er.executor import verify_pairs

    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    n_dev = 8
    W, DIM, MAXLEN = 64, 128, 48

    ds = make_products(1024, seed=5)
    n = ds.n - (ds.n % n_dev)          # shard-divisible prefix
    titles = ds.titles[:n]

    # ---- single-host SN pipeline (catalog executor) ----
    res = run_er(titles, ERConfig(strategy="sorted_neighborhood", window=W,
                                  r=n_dev, feature_dim=DIM, max_len=MAXLEN))

    # ---- RepSN path: sorted row shards + halo exchange ----
    order = sn_sort_order(titles)
    codes, lens = encode_titles(titles, MAXLEN)
    feats = ngram_features(codes, dim=DIM, lengths=lens)
    fs = jnp.asarray(feats[order])
    ca, cb = match_sn_dist(fs, W, mesh, threshold=0.8 - 0.25)
    ha, hb = verify_pairs(codes[order], lens[order], codes[order],
                          lens[order], ca, cb, 0.8)
    got = set()
    for a, b in zip(ha, hb):
        ga, gb = int(order[a]), int(order[b])
        got.add((min(ga, gb), max(ga, gb)))
    assert got == res.matches, (len(got), len(res.matches))
    print("SN dist OK:", len(got), "matches")

    # ---- boundary replication beats all-gather on the wire ----
    halo_bytes, allgather_bytes = sn_replication_volume(n, W, n_dev, DIM)
    assert halo_bytes < allgather_bytes, (halo_bytes, allgather_bytes)
    assert halo_bytes == n_dev * (W - 1) * DIM * 4
    print(f"SN volume OK: halo {halo_bytes} < all-gather {allgather_bytes}")

    # ---- multi-hop: window wider than a shard (w − 1 > n / n_dev) ----
    W2 = n // n_dev + 2
    res2 = run_er(titles, ERConfig(strategy="sorted_neighborhood",
                                   window=W2, r=n_dev, feature_dim=DIM,
                                   max_len=MAXLEN))
    ca, cb = match_sn_dist(fs, W2, mesh, threshold=0.8 - 0.25)
    ha, hb = verify_pairs(codes[order], lens[order], codes[order],
                          lens[order], ca, cb, 0.8)
    got2 = set()
    for a, b in zip(ha, hb):
        ga, gb = int(order[a]), int(order[b])
        got2.add((min(ga, gb), max(ga, gb)))
    assert got2 == res2.matches, (len(got2), len(res2.matches))
    per_hop = sn_replication_volume(n, W2, n_dev, DIM, per_hop=True)
    assert len(per_hop) == 2 and sum(per_hop) == (W2 - 1) * DIM * 4
    print("SN multi-hop OK:", len(got2), "matches over", len(per_hop),
          "hops")
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
