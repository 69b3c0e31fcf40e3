"""Device time of the neighbour exchange per job, on the busiest device:
the ``collective-permute`` ops of the RepSN halo
(``er/compiler`` ``make_scorer(mode="halo")``)."""
from xplane import op_seconds

PERMUTE = r"collective-permute"


def read(rec):
    tr = rec.get("trace")
    if tr is None or rec.get("kind") != "dedup" or rec["chips"] < 2:
        return None
    s = max(op_seconds(tr, d, PERMUTE) for d in range(rec["chips"]))
    return 1e3 * s / len(rec["jobs"]) if s > 0 else None
