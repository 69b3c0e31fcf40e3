"""``run_er`` and ``ERService`` on a data mesh, as the four-chip cells
run them: ``run_er(titles, cfg, mesh=make_er_mesh(n))``.

The mesh cases share one subprocess (the device count must be pinned
before JAX initializes; this session runs on one device). It runs every
case on four simulated CPU devices and writes one JSON record per case;
an exception is recorded against its case, so one failure cannot hide
the others. The parametrised tests assert on the records.

Every mesh result must equal the one-device result (``mesh=None``):
Basic, BlockSplit and PairRange on DS1- and DS2-shaped corpora over 1, 2
and 4 devices, the match_⊥ job for records without a blocking key, both
schedule policies, and the sharded-index service's streaming contract
with zero recompiles after ``warmup()``.
"""
import json
import os
import subprocess
import sys
import time
import traceback

import jax
import pytest

from repro.sharding import make_er_mesh

N_DEV = 4
DIM, MAXLEN = 128, 48
STRATEGIES = ("basic", "block_split", "pair_range")
CORPORA = ("products", "publications")
N_DATA = (1, 2, 4)
POLICIES = ("cost_lpt", "round_robin")
SERVICE_STRATEGIES = ("pair_range", "block_split")
SERVICE_BATCHES = (9, 33, 13, 7)


def _corpus(name):
    from repro.er import make_products, make_publications
    if name == "products":
        return list(make_products(1024, seed=5).titles)
    return list(make_publications(1536, seed=6).titles)


def _with_null_keys(titles):
    """About 5% of the records lose their blocking key: blank titles and
    whitespace-only ones of 4 to 6 spaces (those still match each other,
    so the match_⊥ job has pairs to find)."""
    titles = list(titles)
    null = list(range(7, len(titles), 20))
    for k, i in enumerate(null):
        titles[i] = "" if k % 2 else " " * (4 + k % 3)
    return titles, set(null)


def _moved(before, after):
    return {k: after[k] - before[k] for k in ("flat_bytes", "halo_bytes")}


def _mesh_cases(emit):
    from repro.er import ERConfig, run_er
    from repro.er.compiler import stage1_stats

    def cfg_for(strategy, **kw):
        return ERConfig(strategy=strategy, r=16, m=8, feature_dim=DIM,
                        max_len=MAXLEN, **kw)

    for strategy in STRATEGIES:
        for corpus in CORPORA:
            titles = _corpus(corpus)
            cfg = cfg_for(strategy)
            try:
                one = run_er(titles, cfg).matches
                ref = run_er(titles, cfg_for(strategy,
                                             executor="reference")).matches
            except Exception:
                err = traceback.format_exc()
                for n_data in N_DATA:
                    emit(f"equal/{strategy}-{corpus}-{n_data}",
                         lambda: {"error": err})
                continue

            def case(n_data):
                before = dict(stage1_stats["interconnect"])
                res = run_er(titles, cfg, mesh=make_er_mesh(n_data))
                return {"n_matches": len(res.matches),
                        "diff_one": len(res.matches ^ one),
                        "diff_ref": len(res.matches ^ ref),
                        "total_cost": res.schedule["total_cost"],
                        "total_pairs": res.total_pairs,
                        **_moved(before, stage1_stats["interconnect"])}

            for n_data in N_DATA:
                emit(f"equal/{strategy}-{corpus}-{n_data}",
                     lambda: case(n_data))

    titles, null = _with_null_keys(_corpus("products"))
    for strategy in STRATEGIES:
        def null_case():
            cfg = cfg_for(strategy, match_missing_keys=True)
            got = run_er(titles, cfg, mesh=make_er_mesh(N_DEV)).matches
            one = run_er(titles, cfg).matches
            return {"n_matches": len(got), "diff_one": len(got ^ one),
                    "null_matches": sum(a in null or b in null
                                        for a, b in got)}
        emit(f"null_key/{strategy}", null_case)

    titles = _corpus("products")
    for policy in POLICIES:
        def policy_case():
            cfg = cfg_for("pair_range", schedule_policy=policy)
            res = run_er(titles, cfg, mesh=make_er_mesh(N_DEV))
            one = run_er(titles, cfg).matches
            return {"n_matches": len(res.matches),
                    "diff_one": len(res.matches ^ one),
                    "policy": res.schedule["policy"]}
        emit(f"policy/{policy}", policy_case)


def _service_cases(emit):
    import numpy as np
    from repro.er import (ERConfig, ERService, ServiceConfig,
                          compile_counter, cross_restrict, make_products,
                          run_er)

    ds = make_products(600, seed=5)
    corpus = list(ds.titles[:500]) + [""]
    queries = list(ds.titles[500:560]) + ["", "@@@ fresh block"]
    oracle = run_er(corpus + queries,
                    ERConfig(feature_dim=DIM, max_len=MAXLEN, r=16, m=8))
    want = cross_restrict(oracle.matches, len(corpus))
    for strategy in SERVICE_STRATEGIES:
        try:
            svc = ERService(corpus, ServiceConfig(
                strategy=strategy, feature_dim=DIM, max_len=MAXLEN, r=16,
                m=8, query_buckets=(8, 32, 64), tile_chunk=64),
                mesh=make_er_mesh(N_DEV))
            svc.warmup()
        except Exception:
            err = traceback.format_exc()
            emit(f"service_stream/{strategy}", lambda: {"error": err})
            emit(f"service_recompiles/{strategy}", lambda: {"error": err})
            continue

        def stream():
            got, off = set(), 0
            for size in SERVICE_BATCHES:
                for a, b in svc.match(queries[off:off + size]):
                    got.add((a, b + off))
                off += size
            return {"n_matches": len(got), "n_want": len(want),
                    "diff": len(got ^ want), "queries": off}

        def recompiles():
            rng = np.random.default_rng(0)
            with compile_counter() as steady:
                for _ in range(20):
                    size = int(rng.integers(1, 65))
                    svc.match([queries[int(rng.integers(0, len(queries)))]
                               for _ in range(size)])
            return {"compiles": steady.count}

        emit(f"service_stream/{strategy}", stream)
        emit(f"service_recompiles/{strategy}", recompiles)


def _worker(out_path):
    """Run every mesh case; one JSON line per case into ``out_path``."""
    assert len(jax.devices()) == N_DEV, jax.devices()
    with open(out_path, "w") as out:
        def emit(case, fn):
            try:
                rec = fn()
            except Exception:
                rec = {"error": traceback.format_exc()}
            out.write(json.dumps({"case": case, **rec}) + "\n")
            out.flush()

        _mesh_cases(emit)
        _service_cases(emit)


@pytest.fixture(scope="module")
def records(tmp_path_factory):
    out = tmp_path_factory.mktemp("mesh_run_er") / "records.jsonl"
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_DEV}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), here, env.get("PYTHONPATH", "")])
    t0 = time.perf_counter()
    proc = subprocess.run([sys.executable, __file__, str(out)], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=root)
    print(f"mesh cases: {time.perf_counter() - t0:.1f} s")
    recs = {}
    if out.exists():
        for line in out.read_text().splitlines():
            rec = json.loads(line)
            recs[rec.pop("case")] = rec
    recs["_proc"] = proc
    return recs


def _record(records, case):
    proc = records["_proc"]
    assert case in records, (f"no record for {case}; worker rc "
                             f"{proc.returncode}\n{proc.stderr[-4000:]}")
    rec = records[case]
    assert "error" not in rec, rec["error"]
    return rec


@pytest.mark.parametrize("n_data", N_DATA)
@pytest.mark.parametrize("corpus", CORPORA)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_run_er_mesh_equals_one_chip(records, strategy, corpus, n_data):
    """The mesh match set equals the one-device and the reference
    executor's; the schedule covers every planned pair; the rows move by
    the flat all-gather (none on a one-device mesh), never by a halo."""
    rec = _record(records, f"equal/{strategy}-{corpus}-{n_data}")
    assert rec["n_matches"] > 0
    assert rec["diff_one"] == 0 and rec["diff_ref"] == 0, rec
    assert rec["total_cost"] == rec["total_pairs"], rec
    assert rec["halo_bytes"] == 0, rec
    assert (rec["flat_bytes"] > 0) == (n_data > 1), rec


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_run_er_mesh_null_key_job(records, strategy):
    """Records without a blocking key (match_⊥) on four devices: the
    same match set as one device, match_⊥ pairs included."""
    rec = _record(records, f"null_key/{strategy}")
    assert rec["null_matches"] > 0, rec
    assert rec["diff_one"] == 0, rec


@pytest.mark.parametrize("policy", POLICIES)
def test_run_er_mesh_schedule_policy(records, policy):
    """Either tile-placement policy gives the one-device match set."""
    rec = _record(records, f"policy/{policy}")
    assert rec["policy"] == policy, rec
    assert rec["n_matches"] > 0 and rec["diff_one"] == 0, rec


@pytest.mark.parametrize("strategy", SERVICE_STRATEGIES)
def test_service_mesh_streaming_equals_batch(records, strategy):
    """The sharded-index service, fed in uneven batches, finds exactly
    the query-vs-corpus pairs of one batch ``run_er`` over both."""
    rec = _record(records, f"service_stream/{strategy}")
    assert rec["queries"] == 62 and rec["n_want"] > 0, rec
    assert rec["diff"] == 0, rec


@pytest.mark.parametrize("strategy", SERVICE_STRATEGIES)
def test_service_mesh_zero_recompiles(records, strategy):
    """After ``warmup()`` no query batch size compiles anything."""
    rec = _record(records, f"service_recompiles/{strategy}")
    assert rec["compiles"] == 0, rec


def test_make_er_mesh_shape():
    mesh = make_er_mesh(1)
    assert mesh.devices.shape == (1, 1)
    assert mesh.axis_names == ("data", "model")


def test_make_er_mesh_refuses_too_few_devices():
    with pytest.raises(ValueError, match="devices"):
        make_er_mesh(len(jax.devices()) + 1)


if __name__ == "__main__":
    _worker(sys.argv[1])
