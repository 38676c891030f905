"""Parameter-server push apply per wave: the add of each leaf of a wave's
deltas into the server's weights, the waits for the snapshot and shard
locks included (repro.obs `ps/push_apply` spans,
ParameterServer.finish_push)."""
from bench import spans


def read(rec):
    return spans.per_wave_ms(rec, "ps", "push_apply")
