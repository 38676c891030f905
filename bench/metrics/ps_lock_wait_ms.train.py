"""Time the parameter server's pushes and pulls waited for its snapshot and
shard locks, per wave (repro.obs `ps/lock_wait` spans, recorded where a
wait passed 10 us; none in the window reads 0)."""
from bench import spans


def read(rec):
    return spans.per_wave_ms(rec, "ps", "lock_wait",
                             marker=("ps", "push_flatten"))
