"""What the readers of the program's own spans share.

A run's record carries the repro.obs Tracer events of its window
(`rec["events"]`: tuples (ph, track, name, t0_s, dur_s, args) on the host's
monotonic clock, cut to the spans that start in the window). A program
that records no such span (an older commit) gives its readers nothing to
read: they return None, never 0.
"""
from __future__ import annotations


def durations(rec, track: str, name: str) -> list:
    """Seconds of each `<track>/<name>` span in the window."""
    return [e[4] for e in rec["events"]
            if e[0] == "X" and e[1] == track and e[2] == name]


def waves(rec) -> int:
    """Waves pushed in the window: the (worker, wave) pairs of the workers'
    `push` spans, which ps_sync_ms.train divides by too."""
    return len({(track, args.get("wave"))
                for ph, track, name, _, _, args in rec["events"]
                if ph == "X" and name == "push" and track.startswith("vw")})


def per_wave_ms(rec, track: str, name: str, marker=None):
    """Milliseconds of the `<track>/<name>` spans per wave pushed in the
    window. Where such a span need not occur every wave, `marker` names a
    span (track, name) that shows the program records them: with the
    marker there, no span reads 0."""
    if rec.get("kind") != "train":
        return None
    spent = durations(rec, track, name)
    if not spent and not durations(rec, *(marker or (track, name))):
        return None
    n = waves(rec)
    if not n:
        return None
    return 1e3 * sum(spent) / n
