"""Corpus records times completed jobs, over the window's wall time."""


def read(rec):
    if rec.get("kind") != "dedup":
        return None
    return rec["records"] * len(rec["jobs"]) / rec["window_s"]
