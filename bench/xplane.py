"""Reduce a JAX profiler trace to what the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load` reads it with ``jax.profiler.ProfileData`` and keeps two
things:

* per device plane (``/device:TPU:<i>``), the op events of its
  ``XLA Ops`` line and the program events of its ``XLA Modules`` line:
  name, start and end in seconds. An op's name is its HLO name, the
  text before `` = `` (``%pair_scores_catalog_compact.1``); an op that
  runs inside another one (the body of a ``while``) is dropped, so op
  times add up without counting twice;
* the host spans the benchmark itself opened with
  ``jax.profiler.TraceAnnotation`` (names starting ``bench.``), which
  bound the traced window and label the device's idle gaps.

Host and device events share the profiler's clock. Everything below is
plain interval arithmetic on those events, so every change reduces a trace
the same way.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Event", "Trace", "load", "newest_xplane", "merge",
           "busy_s", "op_seconds", "op_count", "top_ops", "idle_gaps"]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclass(frozen=True)
class Event:
    name: str
    start: float     # seconds on the profiler's clock
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    """Op and program events per device (sorted by start), the
    benchmark's host spans, and the traced window ``[t0, t1]``."""
    devices: Dict[int, List[Event]]
    spans: List[Event]
    t0: float
    t1: float
    modules: Dict[int, List[Event]] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def ops(self, device: int, modules: bool = False) -> List[Event]:
        """Device ``device``'s op (or program) events clipped to the
        window."""
        out = []
        source = self.modules if modules else self.devices
        for e in source.get(device, []):
            s, t = max(e.start, self.t0), min(e.end, self.t1)
            if t > s:
                out.append(Event(e.name, s, t))
        return out


def newest_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def load(path: str, window_span: str = WINDOW_SPAN) -> Trace:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``). The window is the first host span named
    ``window_span``; without one, the extent of all device ops."""
    from jax.profiler import ProfileData

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    devices: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = sorted((Event(e.name.split(" = ", 1)[0],
                                    e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9)
                              for e in line.events),
                             key=lambda e: (e.start, -e.end))
                into = devices if line.name == OPS_LINE else modules
                into[int(m.group(1))] = outermost(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append(Event(e.name, s,
                                           s + e.duration_ns * 1e-9))
    spans.sort(key=lambda e: e.start)
    window = [s for s in spans if s.name == window_span]
    if window:
        t0, t1 = window[0].start, window[0].end
    else:
        every = [e for evs in devices.values() for e in evs]
        t0 = min((e.start for e in every), default=0.0)
        t1 = max((e.end for e in every), default=0.0)
    return Trace(devices=devices, spans=spans, t0=t0, t1=t1,
                 modules=modules)


def outermost(events: List[Event]) -> List[Event]:
    """Drop the events that lie inside an earlier one (sorted by start,
    longest first)."""
    out: List[Event] = []
    reach = float("-inf")
    for e in events:
        if e.end <= reach:
            continue
        out.append(e)
        reach = e.end
    return out


def merge(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[Tuple[float, float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            if t > out[-1][1]:
                out[-1] = (out[-1][0], t)
        else:
            out.append((s, t))
    return out


def busy_s(trace: Trace, device: int) -> float:
    """Seconds of the window in which some op ran on ``device``."""
    return sum(t - s for s, t in merge(
        [(e.start, e.end) for e in trace.ops(device)]))


def op_seconds(trace: Trace, device: int, pattern: str,
               modules: bool = False) -> float:
    """Summed device time of the ops (or programs) whose name matches
    ``pattern`` (a regular expression, searched), overlaps counted
    once."""
    rx = re.compile(pattern)
    return sum(t - s for s, t in merge(
        [(e.start, e.end) for e in trace.ops(device, modules)
         if rx.search(e.name)]))


def op_count(trace: Trace, device: int, pattern: str,
             modules: bool = False) -> int:
    rx = re.compile(pattern)
    return sum(1 for e in trace.ops(device, modules) if rx.search(e.name))


def top_ops(trace: Trace, limit: int = 10) -> List[Tuple[str, float]]:
    """Op names by device time, summed over devices."""
    tot: Dict[str, float] = {}
    for dev in trace.devices:
        for e in trace.ops(dev):
            tot[e.name] = tot.get(e.name, 0.0) + e.seconds
    return sorted(tot.items(), key=lambda kv: -kv[1])[:limit]


def _label(spans: List[Event], s: float, t: float) -> str:
    """The innermost benchmark span covering the gap's midpoint."""
    mid = 0.5 * (s + t)
    best: Optional[Event] = None
    for sp in spans:
        if sp.start <= mid <= sp.end and sp.name != WINDOW_SPAN:
            if best is None or sp.seconds < best.seconds:
                best = sp
    return best.name if best is not None else WINDOW_SPAN


def idle_gaps(trace: Trace, limit: int = 10) -> List[Tuple[str, float]]:
    """The longest stretches of the window in which no device ran an op,
    each named by the host span the benchmark had open then."""
    busy = merge([(e.start, e.end) for dev in trace.devices
                  for e in trace.ops(dev)])
    gaps, cur = [], trace.t0
    for s, t in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if trace.t1 > cur:
        gaps.append((cur, trace.t1))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [(_label(trace.spans, s, t), t - s) for s, t in gaps[:limit]]
