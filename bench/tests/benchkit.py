"""Helpers for the benchmark's tests: a benchmark tree at test size.

``tiny_tree(tmp, n)`` lays out ``<tmp>/BENCHMARK.json`` and
``<tmp>/bench/`` with the real kinds, metrics and traffic mixes and
every configuration cut to ``n`` records, so ``run.run(..., base=)``
drives a whole run on the CPU in seconds.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


# The serve cell's entries: its files are in bench/, and a change that brings
# the cell into BENCHMARK.json adds these entries alone.
SERVE_ENTRIES = {
    "workloads": [{"name": "ds1_serve_zipf", "config": "ds1_products",
                   "traffic": "serve_open_zipf", "chips": 1, "why": "test"}],
    "end_to_end": [
        {"name": n, "unit": u, "better": b, "bound": 0.25,
         "source": "host_clock", "workloads": ["ds1_serve_zipf"]}
        for n, u, b in (("match_p50_ms", "ms", "lower"),
                        ("match_p95_ms", "ms", "lower"),
                        ("match_queries_per_s", "queries/s", "higher"))],
    "per_layer": [
        {"name": n, "unit": u, "better": b, "source": src, "layer": layer,
         "moves": "match_p95_ms", "workloads": ["ds1_serve_zipf"]}
        for n, u, b, src, layer in (
            ("batch_fill.serve", "%", "higher", "program_counter",
             "er/batcher"),
            ("super_batch_ms.serve", "ms", "lower", "program_span",
             "er/service"),
            ("stage1_kernel_ms.serve", "ms", "lower", "device_trace",
             "kernels/pair_sim catalog kernels"),
            ("device_idle_share.serve", "%", "lower", "device_trace",
             "device"))],
}


def with_serve_cell(spec: dict) -> dict:
    for key, entries in SERVE_ENTRIES.items():
        spec[key] = spec[key] + entries
    return spec


def tiny_tree(tmp: Path, n: int = 1500) -> Path:
    """The ``bench`` directory of a test-size copy of the benchmark, with
    the serve cell's entries added."""
    base = tmp / "bench"
    base.mkdir(parents=True)
    for group in ("kinds", "metrics", "traffic"):
        shutil.copytree(BENCH / group, base / group)
    (base / "configs").mkdir()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        conf["n_records"] = n
        (tmp / c["file"]).write_text(json.dumps(conf))
    (tmp / "BENCHMARK.json").write_text(json.dumps(with_serve_cell(spec)))
    return base


def on_cpu(chips: int):
    """Stands in for ``run.require_chip``: JAX's CPU devices."""
    import jax
    return jax.devices()


def result_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


@pytest.fixture
def restore_jax_config():
    """``run.run`` points JAX's persistent cache at its checkout, as a
    benchmark run must; put back what the test process had."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    if env is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = env
    compilation_cache.reset_cache()
