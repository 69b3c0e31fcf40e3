"""Real queries over padded bucket slots in the traced window, percent
(``ERService.stats``: ``queries`` and ``bucket_hits``)."""


def read(rec):
    s = rec.get("service_stats")
    if not s or not s["slots"]:
        return None
    return 100.0 * s["queries"] / s["slots"]
