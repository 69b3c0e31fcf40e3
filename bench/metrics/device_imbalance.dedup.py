"""Busiest device's busy time over the mean over the cell's devices
(mesh placement: ``er/compiler/schedule``, ``comms``)."""
from xplane import busy_s


def read(rec):
    tr = rec.get("trace")
    if tr is None or rec.get("kind") != "dedup" or rec["chips"] < 2:
        return None
    busy = [busy_s(tr, d) for d in range(rec["chips"])]
    mean = sum(busy) / len(busy)
    return max(busy) / mean if mean > 0 else None
