"""Parameter-server synchronisation per wave: the mean, over the waves a
virtual worker finished in the window, of its `push` plus `pull` spans
(repro.obs Tracer tracks vw*/push and vw*/pull)."""


def read(rec):
    if rec.get("kind") != "train":
        return None
    by = {}
    for ph, track, name, t0, dur, args in rec["events"]:
        if ph == "X" and name in ("push", "pull") and track.startswith("vw"):
            key = (track, args.get("wave"), name)
            by[key] = by.get(key, 0.0) + dur
    waves = {(t, w) for t, w, n in by if n == "push"}
    if not waves:
        return None
    return 1e3 * sum(by.values()) / len(waves)
