"""Megabytes each device received over the interconnect per job, over
every flow (gather, ring, halo, model-axis sum), from the program's own
``stage1_stats["interconnect"]`` counter (``er/compiler/comms``)."""


def read(rec):
    if rec.get("kind") != "dedup" or not rec.get("jobs"):
        return None
    flows = [j.get("interconnect") for j in rec["jobs"]]
    if any(f is None for f in flows) or not any(flows):
        return None
    return sum(sum(f.values()) for f in flows) / 1e6 / len(flows)
