"""Mean host-clock time of one Engine decode call in the window, from the
call to its logits on the host (ServeReport.decode_s / decode_steps)."""


def read(rec):
    w = rec.get("window")
    if not w or not w["decode_calls"]:
        return None
    d = w["decode_calls"]
    return 1e3 * sum(b - a for a, b, _, _ in d) / len(d)
