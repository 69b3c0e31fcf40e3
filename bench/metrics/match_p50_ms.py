"""Median latency, scheduled time to answer, over every request due in
the window; an unanswered request counts as infinitely late."""
from kinds.serve import percentile


def read(rec):
    if rec.get("kind") != "serve":
        return None
    return 1e3 * percentile(rec["latency_s"], 50)
