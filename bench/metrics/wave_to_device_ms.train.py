"""The jitted wave step's call per wave, until it returns: its numpy
parameters go to the device there (repro.obs `wave_step/to_device`
spans, core/wave.py build_local_wave_step)."""
from bench import spans


def read(rec):
    return spans.per_wave_ms(rec, "wave_step", "to_device")
