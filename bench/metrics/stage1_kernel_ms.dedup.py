"""Device time of the stage-1 catalog kernels per job, on the busiest
device (``kernels/pair_sim``)."""
from xplane import op_seconds

KERNEL = r"^%pair_scores_catalog"


def read(rec):
    tr = rec.get("trace")
    if tr is None or rec.get("kind") != "dedup" or not rec["jobs"]:
        return None
    s = max(op_seconds(tr, d, KERNEL) for d in range(rec["chips"]))
    return 1e3 * s / len(rec["jobs"]) if s > 0 else None
