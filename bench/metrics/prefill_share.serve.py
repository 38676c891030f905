"""Share of the window the Scheduler spent in prefill groups: the host-
clock spans of the Engine's prefill calls (each ends when its logits are
on the host) over the window. Decode waits behind each one."""


def read(rec):
    w = rec.get("window")
    if not w:
        return None
    busy = sum(b - a for a, b, _, _ in w["prefill_calls"])
    return 100.0 * busy / w["window_s"]
