"""Parameter-server push, host side, per wave: the copy of every leaf of a
wave's deltas to flat f32 before the update is applied (repro.obs
`ps/push_flatten` spans, ParameterServer.begin_push)."""
from bench import spans


def read(rec):
    return spans.per_wave_ms(rec, "ps", "push_flatten")
