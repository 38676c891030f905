"""Parameter-server pull per wave: the copy of every leaf whose shard moved
into a fresh snapshot, shard-lock waits included (repro.obs
`ps/pull_serve` spans, ParameterServer.pull)."""
from bench import spans


def read(rec):
    return spans.per_wave_ms(rec, "ps", "pull_serve")
