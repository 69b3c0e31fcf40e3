#!/usr/bin/env python3
"""Run the ER system's main paths once on a TPU, at the paper's DS1 scale.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the multi-chip paths, four chips

One chip: batch dedup (``run_er``) of ``make_products(114_000)`` under
PairRange and BlockSplit at the paper's m = 20, r = 100, each match set
compared exactly with ``executor="reference"`` on the same titles; then
a resident ``ERService`` over all but the last 680 titles answers four
``match()`` batches of those titles after ``warmup()``, compared
exactly with the reference restricted to cross pairs, with zero
compiles after warmup. The stage-1 program must hold the Pallas kernel.

Four chips: the same corpus through ``run_er`` on a 4-device data mesh
with the flat and ring gathers, Sorted Neighborhood through the same
``run_er`` (the RepSN halo: single-hop at w = 10 over DS1, multi-hop on
an 8,192-title slice) and the sharded-index ``ERService``, each compared
exactly with the one-chip run of the same job.

Everything runs in this one process, which holds the chip(s). Any
failed phase or comparison exits non-zero. So does a run where JAX finds
no TPU, before anything is computed. Wall times printed here are
timings of one cold smoke run, not benchmark measurements. The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import replace

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.compile_cache import use_compile_cache  # noqa: E402

N_DS1 = 114_000
M_PARTS, R_TASKS = 20, 100        # the paper's DS1 setting (Fig. 9)
QUERY_BATCHES = (8, 32, 128, 512)  # one per default query bucket
SN_SLICE, SN_WIDE_WINDOW = 8_192, 2_500   # w − 1 > 8,192 / 4: two hops


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit still passes through the compile
    event, but in a fraction of the time)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def log(msg: str) -> None:
    print(msg, flush=True)


def phase(name: str, fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    log(f"[{name}] smoke wall time {time.perf_counter() - t0:.3f} s "
        f"(one cold run, not a benchmark)")
    return out


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"FAILED: {msg}")


def stats_snapshot() -> dict:
    from repro.er.compiler import stage1_stats
    return {k: (dict(v) if isinstance(v, dict) else v)
            for k, v in stage1_stats.items()}


def stats_delta(before: dict) -> dict:
    after = stats_snapshot()
    out = {k: after[k] - before[k] for k in after if k != "interconnect"}
    out["interconnect"] = {k: v - before["interconnect"].get(k, 0)
                           for k, v in after["interconnect"].items()
                           if v - before["interconnect"].get(k, 0)}
    return out


def corpus(n: int = N_DS1):
    import numpy as np
    from repro.er import make_products
    from repro.er.blocking import prefix_block_ids
    ds = make_products(n)
    bid, _ = prefix_block_ids(ds.titles, k=ds.prefix_len)
    sizes = np.bincount(bid[bid >= 0])
    sizes = sizes[sizes > 0]
    pairs = sizes * (sizes - 1) // 2
    log(f"DS1 corpus: {ds.n} titles, {sizes.size} blocks, "
        f"{int(pairs.sum())} pairs, largest block {int(sizes.max())} "
        f"records with {pairs.max() / pairs.sum():.4f} of the pairs, "
        f"{len(ds.true_pairs)} injected duplicates")
    return ds


def base_config(ds, impl: str):
    from repro.er import ERConfig
    return ERConfig(r=R_TASKS, m=M_PARTS, prefix_len=ds.prefix_len,
                    kernel_impl=impl)


def stage1_program_text(n_rows: int, cfg) -> str:
    """The compiled stage-1 program ``run_er`` launches per chunk of
    1,024 tiles, at this run's shapes."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import pair_sim
    cap = pair_sim.resolve_capacity(cfg.block_m, cfg.block_n)
    feats = jax.ShapeDtypeStruct((n_rows, cfg.feature_dim), jnp.float32)
    cat = jax.ShapeDtypeStruct((1024, pair_sim.NCOLS), jnp.int32)
    log(f"stage-1 kernel: compact, tile {cfg.block_m}x{cfg.block_n}, "
        f"resolved capacity {cap} slots/tile")
    return pair_sim.pair_scores_catalog_compact.lower(
        feats, feats, cat, threshold=cfg.threshold - cfg.filter_margin,
        block_m=cfg.block_m, block_n=cfg.block_n,
        capacity=cap).compile().as_text()


def batch_dedup(ds, cfg, strategy: str):
    """run_er through the kernel vs the reference executor."""
    from repro.er import run_er
    c = replace(cfg, strategy=strategy)
    before = stats_snapshot()
    t0 = time.perf_counter()
    got = run_er(ds.titles, c)
    t_kernel = time.perf_counter() - t0
    delta = stats_delta(before)
    t0 = time.perf_counter()
    want = run_er(ds.titles, replace(c, executor="reference"))
    t_ref = time.perf_counter() - t0
    recall = len(got.matches & ds.true_pairs) / len(ds.true_pairs)
    log(f"run_er {strategy}: {got.extra['catalog_tiles']} tiles, "
        f"{len(got.matches)} matches, reference {len(want.matches)}, "
        f"equal={got.matches == want.matches}, recall vs true_pairs "
        f"{recall:.6f}; smoke wall {t_kernel:.3f} s kernel path, "
        f"{t_ref:.3f} s reference")
    log(f"run_er {strategy} stage1_stats: {delta}")
    check(got.matches == want.matches,
          f"run_er {strategy} differs from the reference: "
          f"{len(got.matches ^ want.matches)} pairs in the symmetric "
          f"difference")
    check(delta["compact_decodes"] > 0, "the compact kernel never ran")
    return want.matches


def serve(ds, cfg, want_all, mesh=None):
    """ERService over all but the last queries; the streamed answers
    must equal the batch match set restricted to cross pairs."""
    from repro.er import (ERService, ServiceConfig, compile_counter,
                          cross_restrict)
    n_q = sum(QUERY_BATCHES)
    corpus_titles, queries = ds.titles[:-n_q], ds.titles[-n_q:]
    svc = ERService(corpus_titles, ServiceConfig(
        prefix_len=cfg.prefix_len, kernel_impl=cfg.kernel_impl), mesh=mesh)
    t0 = time.perf_counter()
    svc.warmup()
    log(f"service warmup: smoke wall {time.perf_counter() - t0:.3f} s")
    got, off = set(), 0
    before = stats_snapshot()
    with compile_counter() as cc:
        for size in QUERY_BATCHES:
            t0 = time.perf_counter()
            resp = svc.match(queries[off:off + size])
            log(f"service match({size}): {len(resp)} matches, smoke wall "
                f"{time.perf_counter() - t0:.3f} s")
            got |= {(a, b + off) for a, b in resp}
            off += size
    want = cross_restrict(want_all, len(corpus_titles))
    log(f"service: {len(got)} matches over {n_q} queries, oracle "
        f"{len(want)}, equal={got == want}, compiles after warmup "
        f"{cc.count}, stage1_stats {stats_delta(before)}")
    check(got == want, f"service answers differ from the oracle: "
          f"{len(got ^ want)} pairs in the symmetric difference")
    check(cc.count == 0, f"{cc.count} compiles after warmup")


def sn_halo(ds, impl: str, mesh, n: int, window: int):
    """SN through run_er on the mesh (the RepSN halo) vs one chip."""
    from repro.er import ERConfig, run_er
    n_dev = int(mesh.shape["data"])
    titles = ds.titles[:n]
    cfg = ERConfig(strategy="sorted_neighborhood", window=window,
                   r=R_TASKS, kernel_impl=impl)
    want = run_er(titles, cfg).matches
    before = stats_snapshot()
    got = run_er(titles, cfg, mesh=mesh).matches
    moved = stats_delta(before)
    hops = -(-(window - 1) // -(-len(titles) // n_dev))
    log(f"SN halo n={len(titles)} w={window} ({hops} hop(s)): "
        f"{len(got)} matches, one chip {len(want)}, equal={got == want}, "
        f"stage1_stats {moved}")
    check(got == want, f"SN halo w={window} differs from one chip")
    check(moved["interconnect"].get("flat_bytes", 0) == 0,
          f"SN halo w={window} all-gathered the corpus")


def mesh_self_join(ds, cfg, mesh, comms: str, want):
    from repro.er import run_er
    before = stats_snapshot()
    got = run_er(ds.titles, replace(cfg, comms=comms), mesh=mesh)
    log(f"run_er mesh comms={comms}: {len(got.matches)} matches, one chip "
        f"{len(want)}, equal={got.matches == want}, fallback "
        f"{got.extra.get('comms_fallback')}, stage1_stats "
        f"{stats_delta(before)}")
    check(got.matches == want, f"mesh comms={comms} differs from one chip")
    check(comms == "flat" or "comms_fallback" not in got.extra,
          f"comms={comms} fell back to flat: "
          f"{got.extra.get('comms_fallback')}")


def peak_bytes(devices) -> list:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def one_chip(ds, impl: str) -> None:
    cfg = base_config(ds, impl)
    text = phase("stage-1 program", stage1_program_text,
                 len(ds.titles), cfg)
    check("tpu_custom_call" in text,
          "the stage-1 program holds no tpu_custom_call")
    log("stage-1 program holds tpu_custom_call: True")
    want = None
    for strategy in ("pair_range", "block_split"):
        want = phase(f"run_er {strategy}", batch_dedup, ds, cfg, strategy)
    phase("service", serve, ds, cfg, want)


def four_chips(ds, impl: str) -> None:
    import jax
    from repro.er import run_er
    from repro.sharding import make_er_mesh
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
          f"{len(devices)}")
    cfg = base_config(ds, impl)
    want = phase("one-chip run_er", run_er, ds.titles, cfg).matches
    log(f"one-chip run_er pair_range: {len(want)} matches")
    mesh = make_er_mesh(4)
    for comms in ("flat", "ring"):
        phase(f"mesh run_er {comms}", mesh_self_join, ds, cfg, mesh,
              comms, want)
    phase("SN halo w=10", sn_halo, ds, impl, mesh, len(ds.titles), 10)
    phase(f"SN halo w={SN_WIDE_WINDOW}", sn_halo, ds, impl, mesh,
          SN_SLICE, SN_WIDE_WINDOW)
    phase("sharded service", serve, ds, cfg, want, mesh=mesh)
    peaks = peak_bytes(devices[:4])
    shard = len(ds.titles) * cfg.feature_dim * 4 // 4
    log(f"peak device bytes in use: {peaks} (a row shard of the "
        f"features is {shard} B)")
    check(min(peaks) >= shard, "a mesh device never held its shard")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)

    cache_dir = use_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX's default device is "
              f"{dev.platform!r} ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        return 1
    clock = CompileClock()
    log(f"jax {jax.__version__}, device_kind {dev.device_kind!r}, "
        f"{len(jax.devices())} device(s), compile cache {cache_dir}")

    t0 = time.perf_counter()
    ds = phase("build DS1", corpus)
    if args.chips == 4:
        four_chips(ds, "pallas")
    else:
        one_chip(ds, "pallas")
    log(f"total smoke wall {time.perf_counter() - t0:.3f} s; backend "
        f"compile {clock.seconds:.3f} s over {clock.compiles} compiles, "
        f"{clock.cache_hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
