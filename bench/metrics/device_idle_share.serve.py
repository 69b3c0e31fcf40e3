"""1 - busy / window, averaged over the cell's devices, in percent."""
from xplane import busy_s


def read(rec):
    tr = rec.get("trace")
    if tr is None or rec.get("kind") != "serve" or tr.window_s <= 0:
        return None
    busy = [busy_s(tr, d) for d in range(rec["chips"])]
    if not any(busy):
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / tr.window_s)
