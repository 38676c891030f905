"""Time a virtual worker waited for the device lock of the shared wave step,
per wave: the other worker's step runs meanwhile (repro.obs
`wave_step/lock_wait` spans, core/wave.py build_local_wave_step)."""
from bench import spans


def read(rec):
    return spans.per_wave_ms(rec, "wave_step", "lock_wait")
