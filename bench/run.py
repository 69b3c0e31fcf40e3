#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload ds1_dedup --seed 7 --seconds 10 --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything
else is found by name, so a cell, configuration, traffic mix or metric
is added with files and entries alone:

    bench/configs/<config>.json    the deployment: corpus, plan, matcher
    bench/traffic/<traffic>.json   the mix; its "kind" names the code
    bench/kinds/<kind>.py          drives the program (run) and judges
                                   what it produced (check)
    bench/metrics/<metric>.py      read(rec) -> number or None

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window. Each run
checks its answers against ``bench/reference.py`` after the window has
closed, and prints every number compared beside its limit as the last
lines of standard error and under ``checks`` in the result line, which
is the last line of standard output. With no TPU, or fewer chips than
the cell asks for, it exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import contextmanager, nullcontext  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

EXIT_NO_CHIP = 3


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    root: Path = ROOT


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(group: str, name: str, base: Path = BENCH):
    """``<base>/<group>/<name>.py`` as a module (names may hold dots)."""
    path = base / group / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {group} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{group}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(spec: dict, name: str, seed: int, seconds: float,
              trace: bool, base: Path = BENCH) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((base.parent / configs[w["config"]]["file"])
                        .read_text())
    traffic = json.loads((base / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    return Cell(name=name, config=config, traffic=traffic,
                chips=int(w["chips"]), seed=int(seed),
                seconds=float(seconds), trace=bool(trace),
                root=base.parent)


def cell_metrics(spec: dict, name: str, trace: bool) -> List[dict]:
    """The metrics a cell reports: end-to-end ones with ``--trace 0``,
    per-layer ones with ``--trace 1``."""
    def listed(m):
        return "workloads" not in m or name in m["workloads"]
    e2e = [m for m in spec["end_to_end"] if listed(m)]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def require_chip(chips: int):
    """The devices a cell runs on; raises :class:`NoChip` unless JAX's
    devices are TPUs and there are at least ``chips`` of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's default device is {devices[0].platform!r} "
                     f"({devices[0].device_kind}), not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices


class Harness:
    """What a traffic kind gets from the harness: the clock that ends
    set-up, the profiler around the traced window, host spans, a count
    of backend compiles, and the seconds spent in Python's collector."""

    def __init__(self, cell: Cell):
        import jax
        self.cell = cell
        self.setup_s: Optional[float] = None
        self.compiles = 0
        self.gc_s = 0.0
        self._gc_t0 = 0.0
        self.trace_dir = cell.root / ".bench_out" / "trace" / cell.name
        self._tracing = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        gc.callbacks.append(self._on_gc)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        gc.callbacks.remove(self._on_gc)

    def _on_event(self, name: str, secs: float, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_s += time.perf_counter() - self._gc_t0

    def window_opens(self) -> None:
        """Set-up ends here: process start to the measured window."""
        self.setup_s = time.perf_counter() - _T_START

    @contextmanager
    def profile(self):
        """The profiler around the traced window, which is marked by the
        host span ``bench.window``."""
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        self._tracing = True
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            self._tracing = False
            jax.profiler.stop_trace()

    def span(self, name: str):
        """A host span ``bench.<name>`` in the trace, when tracing."""
        if not self._tracing:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def read_metrics(metrics: List[dict], rec: dict, base: Path = BENCH
                 ) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        value = load_module("metrics", m["name"], base).read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(argv=None, require: Callable = require_chip,
        base: Path = BENCH) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    spec = json.loads((base.parent / "BENCHMARK.json").read_text())
    cell = load_cell(spec, args.workload, args.seed, args.seconds,
                     bool(args.trace), base)
    metrics = cell_metrics(spec, cell.name, cell.trace)
    kind = load_module("kinds", cell.traffic["kind"], base)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cell.root / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    import repro.er  # noqa: F401  (the system under test must be here)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(cell.root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = require(cell.chips)
    except NoChip as e:
        log(f"bench: {e}; nothing was run")
        return EXIT_NO_CHIP
    used = devices[:cell.chips]
    dev = devices[0]
    log(f"bench: {cell.name} seed {cell.seed} seconds {cell.seconds:g} "
        f"trace {int(cell.trace)} on {len(devices)} x {dev.device_kind}")

    h = Harness(cell)
    try:
        rec = kind.run(cell, h)
    finally:
        h.close()
    rec.update(cell=cell.name, chips=cell.chips, setup_s=h.setup_s,
               device_kind=dev.device_kind)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes(used)}
    breakdown = None
    if cell.trace:
        from xplane import busy_s, idle_gaps, load, newest_xplane, top_ops
        tr = load(newest_xplane(str(h.trace_dir)))
        rec["trace"] = tr
        busy = [busy_s(tr, i) for i in range(cell.chips)]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": [list(x) for x in top_ops(tr)],
                     "idle_gaps": [list(x) for x in idle_gaps(tr)]}
        log(f"bench: trace {tr.window_s:.6f} s window, busy per device "
            f"{busy}")
    values = read_metrics(metrics, rec, base)
    for name, v in values.items():
        log(f"metric {name} = {v['value']!r} {v['unit']}")

    kind.release(rec)
    gc.collect()
    checks = kind.check(cell, rec)
    correct = all(c["value"] <= c["limit"] for c in checks)
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})")
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": values, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
