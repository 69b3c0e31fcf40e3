"""Device time of the stage-2 edit-similarity program per job
(``verify_pairs`` -> ``er/similarity``), on the busiest device."""
from xplane import op_seconds

PROGRAM = r"^jit_edit_distance\("


def read(rec):
    tr = rec.get("trace")
    if tr is None or rec.get("kind") != "dedup" or not rec["jobs"]:
        return None
    s = max(op_seconds(tr, d, PROGRAM, modules=True)
            for d in range(rec["chips"]))
    return 1e3 * s / len(rec["jobs"]) if s > 0 else None
