"""The program's own host spans (``er.*``) in a traced run.

The program brackets each phase of a job in a
``jax.profiler.TraceAnnotation`` named ``er.<phase>``
(``src/repro/er/trace.py``), with counts of its work as arguments. They
land in the same ``.xplane.pb`` as the device ops and the benchmark's
``bench.*`` spans, on the same clock. :func:`load` reads them with their
arguments and where each nests (the names of the ``er.*`` spans open
around it on its thread); :func:`of_run` finds the traced run's file
where ``Harness`` wrote it, ``<root>/.bench_out/trace/<cell>``, and
clips the spans to the run's ``bench.window``. A file is parsed once.

The readers in ``metrics/*.dedup.py`` reduce the clipped spans to
milliseconds per job with the helpers below. A trace with no ``er.*``
span (a program without them) reads as nothing.
"""
from __future__ import annotations

import functools
import gzip
import os
import warnings
from dataclasses import dataclass, replace
from pathlib import Path
from typing import Callable, Iterable, List, Optional, Tuple

from xplane import Event, Trace, merge, newest_xplane

__all__ = ["Span", "load", "of_run", "per_job_ms", "seconds",
           "seconds_outside", "idle_unattributed_s", "PREFIX", "ROOT_SPAN"]

PREFIX = "er."
ROOT_SPAN = "er.run_er"


@dataclass(frozen=True)
class Span(Event):
    """A host span: an :class:`~xplane.Event` with its arguments and the
    names of the spans it lies in on its thread."""
    path: Tuple[str, ...] = ()   # outermost first
    args: Tuple[Tuple[str, object], ...] = ()

    def arg(self, key: str, default=None):
        return dict(self.args).get(key, default)


def _nest(events: List[Tuple[float, float, str, tuple]]) -> List[Span]:
    """Spans of one thread with the names of the spans they lie in."""
    out: List[Span] = []
    stack: List[Span] = []
    for s, t, name, args in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1].end <= s:
            stack.pop()
        sp = Span(name, s, t, tuple(p.name for p in stack), args)
        out.append(sp)
        stack.append(sp)
    return out


def load(path: str) -> Tuple[Span, ...]:
    """Every ``er.*`` host span of an ``.xplane.pb`` (or ``.xplane.pb.gz``),
    sorted by start."""
    return _parse(os.path.abspath(path), os.path.getmtime(path))


@functools.lru_cache(maxsize=1)
def _parse(path: str, mtime: float) -> Tuple[Span, ...]:
    from jax.profiler import ProfileData

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    spans: List[Span] = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                evs = [(e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9, e.name,
                        tuple(e.stats))
                       for e in line.events if e.name.startswith(PREFIX)]
                spans += _nest(evs)
    return tuple(sorted(spans, key=lambda s: (s.start, -s.end)))


def of_run(rec: dict, root: Path) -> List[Span]:
    """The ``er.*`` spans of a traced run's window, clipped to it; empty
    when the run was not traced or the trace holds none."""
    tr: Optional[Trace] = rec.get("trace")
    if tr is None:
        return []
    try:
        path = newest_xplane(str(Path(root) / ".bench_out" / "trace"
                                 / rec["cell"]))
    except FileNotFoundError:
        return []
    out = []
    for s in load(path):
        a, b = max(s.start, tr.t0), min(s.end, tr.t1)
        if b > a:
            out.append(replace(s, start=a, end=b))
    return out


def per_job_ms(rec: dict, metric_file: str,
               seconds_of: Callable[[List[Span], Trace], float]
               ) -> Optional[float]:
    """``seconds_of(spans, trace)`` in milliseconds per job, for a traced
    dedup run whose trace holds the program's spans; else None. The
    checkout's root is found from the metric's own file
    (``<root>/bench/metrics/<name>.py``), as ``Harness`` writes the
    trace under the root of the ``bench`` directory it runs from."""
    if rec.get("kind") != "dedup" or not rec.get("jobs"):
        return None
    spans = of_run(rec, Path(metric_file).resolve().parents[2])
    if not any(s.name == ROOT_SPAN for s in spans):
        return None
    return 1e3 * seconds_of(spans, rec["trace"]) / len(rec["jobs"])


def seconds(spans: Iterable[Span], *names: str) -> float:
    """Summed time of the spans with these names."""
    return sum(s.seconds for s in spans if s.name in names)


def seconds_outside(spans: List[Span], name: str, child: str) -> float:
    """Time of the ``name`` spans less that of their direct ``child``
    spans."""
    return (seconds(spans, name)
            - sum(s.seconds for s in spans
                  if s.name == child and s.path and s.path[-1] == name))


def idle_unattributed_s(spans: List[Span], trace: Trace) -> float:
    """Window time in which no device ran an op and no ``er.*`` span
    other than ``er.run_er`` was open."""
    covered = [(e.start, e.end) for d in trace.devices
               for e in trace.ops(d)]
    covered += [(s.start, s.end) for s in spans if s.name != ROOT_SPAN]
    return trace.window_s - sum(t - s for s, t in merge(covered))

