"""Device time of the stage-1 catalog kernels per super-batch slice
(``kernels/pair_sim``)."""
from xplane import op_seconds

KERNEL = r"^%pair_scores_catalog"


def read(rec):
    tr = rec.get("trace")
    s = rec.get("service_stats")
    if tr is None or rec.get("kind") != "serve" or not s or not s["batches"]:
        return None
    k = max(op_seconds(tr, d, KERNEL) for d in range(rec["chips"]))
    return 1e3 * k / s["batches"] if k > 0 else None
