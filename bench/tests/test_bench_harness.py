"""The harness on the CPU: discovery by name, refusal without a chip,
and ``correct`` coming out false when the timed path is broken."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

import benchkit
from benchkit import on_cpu, restore_jax_config, result_line, tiny_tree  # noqa: F401
import run

pytestmark = pytest.mark.usefixtures("restore_jax_config")


def test_new_config_mix_and_metric_are_files_alone(tmp_path, capsys):
    """A configuration, a traffic mix, a metric and a cell added as new
    files and entries run without an edit to any existing file."""
    base = tiny_tree(tmp_path)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    conf = json.loads((base / "configs" / "ds1_products.json").read_text())
    conf.update(name="ds1_other", n_records=1200)
    (base / "configs" / "ds1_other.json").write_text(json.dumps(conf))
    (base / "traffic" / "dedup_other.json").write_text(json.dumps(
        {"kind": "dedup", "about": "test"}))
    (base / "metrics" / "jobs_in_window.py").write_text(
        "def read(rec):\n    return len(rec['jobs'])\n")
    spec["configs"].append({"name": "ds1_other", "source": "test",
                            "file": "bench/configs/ds1_other.json",
                            "reduced": ["n_records"], "why": "test"})
    spec["workloads"].append({"name": "other", "config": "ds1_other",
                              "traffic": "dedup_other", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "jobs_in_window", "unit": "jobs",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["other"]})
    spec["end_to_end"][0]["workloads"].append("other")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    assert run.run(["--workload", "other", "--seed", "3", "--seconds",
                    "0.1"], require=on_cpu, base=base) == 0
    res = result_line(capsys.readouterr().out)
    assert res["correct"] is True
    assert res["metrics"]["jobs_in_window"]["value"] == 1
    assert {"dedup_records_per_s", "setup_s", "jobs_in_window"} == set(
        res["metrics"])


def test_cell_metrics_follow_workloads():
    spec = benchkit.with_serve_cell(
        json.loads((benchkit.ROOT / "BENCHMARK.json").read_text()))
    e2e = {m["name"] for m in run.cell_metrics(spec, "ds1_serve_zipf", False)}
    assert e2e == {"match_p50_ms", "match_p95_ms", "match_queries_per_s",
                   "setup_s"}
    layer = {m["name"] for m in run.cell_metrics(spec, "ds1_dedup", True)}
    assert "stage1_kernel_ms.dedup" in layer
    assert "batch_fill.serve" not in layer
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (benchkit.BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_no_chip_no_result(capsys):
    """On the CPU the harness exits non-zero before any work and prints
    no result."""
    assert run.run(["--workload", "ds1_dedup", "--seed", "1",
                    "--seconds", "1"]) == run.EXIT_NO_CHIP
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no program
    to measure: non-zero exit, no result line."""
    import shutil
    shutil.copytree(benchkit.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(benchkit.ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "ds1_dedup", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


# -- a broken timed path must read as not correct -------------------------

def _drop_half_of_each_batch(monkeypatch):
    from repro.er import service
    real = service.ERService._execute_batch

    def broken(self, pb, ctx):
        out = real(self, pb, ctx)
        keep = {(a, b) for a, b in out if b < pb.nq // 2}
        out.clear()
        out.update(keep)
        return out
    monkeypatch.setattr(service.ERService, "_execute_batch", broken)


def _alter_one_answer(monkeypatch):
    from repro.er import service
    real = service.ERService._execute_batch

    def broken(self, pb, ctx):
        out = real(self, pb, ctx)
        if out:
            out.discard(next(iter(sorted(out))))
        return out
    monkeypatch.setattr(service.ERService, "_execute_batch", broken)


def _dedup_half_the_records(monkeypatch):
    import repro.er
    real = repro.er.run_er

    def broken(titles, *a, **k):
        return real(titles[:len(titles) // 2], *a, **k)
    monkeypatch.setattr(repro.er, "run_er", broken)


def _dedup_alter_one_answer(monkeypatch):
    import repro.er
    real = repro.er.run_er

    def broken(titles, *a, **k):
        res = real(titles, *a, **k)
        res.matches.discard(min(res.matches))
        return res
    monkeypatch.setattr(repro.er, "run_er", broken)


@pytest.mark.parametrize("cell,fault", [
    ("ds1_dedup", _dedup_half_the_records),
    ("ds1_dedup", _dedup_alter_one_answer),
    ("ds1_serve_zipf", _drop_half_of_each_batch),
    ("ds1_serve_zipf", _alter_one_answer),
], ids=["dedup-half-batch", "dedup-altered-answer", "serve-half-batch",
        "serve-altered-answer"])
def test_broken_timed_path_is_not_correct(tmp_path, capsys, monkeypatch,
                                          cell, fault):
    base = tiny_tree(tmp_path)
    argv = ["--workload", cell, "--seed", "2024", "--seconds", "0.5"]
    assert run.run(argv, require=on_cpu, base=base) == 0
    assert result_line(capsys.readouterr().out)["correct"] is True
    fault(monkeypatch)
    assert run.run(argv, require=on_cpu, base=base) == 0
    res = result_line(capsys.readouterr().out)
    assert res["correct"] is False
    assert res["checks"]["mismatched_pairs"]["value"] > 0


MESH_RUN = textwrap.dedent("""
    import json
    import sys
    from pathlib import Path
    sys.path.insert(0, {tests!r})
    from benchkit import on_cpu, tiny_tree
    import run
    import jax, jax.numpy as jnp
    assert len(jax.devices()) == 4
    if {broken!r}:
        # the exchange between chips left out: each device gathers
        # copies of its own shard in place of the others'
        def all_gather(x, axis_name, *, tiled=False, **kw):
            n = jax.lax.psum(1, axis_name)
            return jnp.concatenate([x] * n, 0) if tiled else jnp.stack([x] * n)
        jax.lax.all_gather = all_gather
    base = tiny_tree(Path({tmp!r}), n=2000)
    # the four-chip cell is a workloads entry over the dedup_mesh4 mix
    spec = json.loads((base.parent / "BENCHMARK.json").read_text())
    spec["workloads"].append({{"name": "ds1_dedup_mesh4",
                              "config": "ds1_products",
                              "traffic": "dedup_mesh4", "chips": 4,
                              "why": "test"}})
    spec["end_to_end"][0]["workloads"].append("ds1_dedup_mesh4")
    (base.parent / "BENCHMARK.json").write_text(json.dumps(spec))
    sys.exit(run.run(["--workload", "ds1_dedup_mesh4", "--seed", "77",
                      "--seconds", "0.1"], require=on_cpu, base=base))
""")


@pytest.mark.parametrize("broken", [False, True],
                         ids=["mesh-sound", "mesh-exchange-left-out"])
def test_mesh_exchange_left_out_is_not_correct(tmp_path, broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = MESH_RUN.format(tests=str(Path(__file__).parent),
                             tmp=str(tmp_path), broken=broken)
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert result_line(p.stdout)["correct"] is (not broken)
