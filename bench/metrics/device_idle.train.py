"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window (bench/trace_reduce.py)."""


def read(rec):
    t = rec.get("trace")
    if not t or not t["devices"] or rec.get("kind") != "train":
        return None
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
