"""Host time per super-batch slice, plan to answer (``ERService.stats``:
``seconds`` over ``batches``)."""


def read(rec):
    s = rec.get("service_stats")
    if not s or not s["batches"]:
        return None
    return 1e3 * s["seconds"] / s["batches"]
