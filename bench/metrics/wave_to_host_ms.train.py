"""The copy of a wave's deltas from the device to host numpy, per wave,
after the step has finished on the device (repro.obs `wave_step/to_host`
spans, core/wave.py build_local_wave_step)."""
from bench import spans


def read(rec):
    return spans.per_wave_ms(rec, "wave_step", "to_host")
