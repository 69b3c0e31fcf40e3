"""Device time of collective ops per job, on the busiest device
(``er/compiler/comms``)."""
from xplane import op_seconds

COLLECTIVE = r"all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all"


def read(rec):
    tr = rec.get("trace")
    if tr is None or rec.get("kind") != "dedup" or rec["chips"] < 2:
        return None
    s = max(op_seconds(tr, d, COLLECTIVE) for d in range(rec["chips"]))
    return 1e3 * s / len(rec["jobs"]) if s > 0 else None
