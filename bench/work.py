"""The work a layer must do, counted from the job, and the chip's peaks.

The stage-1 count is the algorithm's: every live pair of the plan gets
one ``d``-wide dot (2·d operations), and every corpus row's features
are read once (``4·d`` bytes in float32). It depends on the plan and the
corpus only, never on tiles, geometry, capacity or precision, so any
later implementation of stage 1 is held to the same count.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

__all__ = ["peaks", "stage1_work", "roofline_percent"]

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Published per-chip peaks for ``device_kind``; an unknown kind is
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def stage1_work(live_pairs: int, rows: int, feature_dim: int
                ) -> Tuple[float, float]:
    """(operations, bytes) stage 1 needs for one job."""
    return 2.0 * live_pairs * feature_dim, 4.0 * rows * feature_dim


def roofline_percent(ops: float, nbytes: float, seconds: float,
                     chip: Dict[str, float], chips: int = 1) -> float:
    """The least time ``chips`` chips need for the work, as a percentage
    of the time it took."""
    least = max(ops / (chips * chip["bf16_flops_per_s"]),
                nbytes / (chips * chip["hbm_bytes_per_s"]))
    return 100.0 * least / seconds
