"""Stage 1's share of its roofline: the least time the chips need for
the plan's live pairs (``bench/work.py``), over the stage-1 kernels'
device time on the busiest device."""
from work import peaks, roofline_percent, stage1_work
from xplane import op_seconds

KERNEL = r"^%pair_scores_catalog"


def read(rec):
    tr = rec.get("trace")
    if tr is None or rec.get("kind") != "dedup" or not rec["jobs"]:
        return None
    s = max(op_seconds(tr, d, KERNEL) for d in range(rec["chips"]))
    if s <= 0:
        return None
    ops = nbytes = 0.0
    for j in rec["jobs"]:
        o, b = stage1_work(j["live_pairs"], rec["records"],
                           rec["feature_dim"])
        ops, nbytes = ops + o, nbytes + b
    return roofline_percent(ops, nbytes, s, peaks(rec["device_kind"]),
                            rec["chips"])
