"""Time a virtual worker waited at the WSP staleness gate, per wave it
started in the window (repro.obs `gate_wait` spans; waves that passed the
gate at once record none and count as 0)."""


def read(rec):
    if rec.get("kind") != "train":
        return None
    waits = [dur for ph, track, name, t0, dur, _ in rec["events"]
             if ph == "X" and name == "gate_wait"]
    waves = [1 for ph, track, name, *_ in rec["events"]
             if ph == "X" and name == "wave"]
    if not waves:
        return None
    return 1e3 * sum(waits) / len(waves)
