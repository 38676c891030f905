"""Device time of the jitted local wave step (core/wave.py,
build_local_wave_step) per wave: the trace's `jit_wave_step` program runs
over the waves that landed in the traced window."""


def read(rec):
    t = rec.get("trace")
    if not t or not t["devices"] or rec.get("kind") != "train":
        return None
    if not rec["waves_in_window"]:
        return None
    s = sum(v for n, v in t["modules"].items() if "wave_step" in n)
    if s <= 0:
        return None
    return 1e3 * s / rec["waves_in_window"]
