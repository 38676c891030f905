"""Host time to dispatch one paged decode step: the jitted call alone,
until it returns, inside the Engine's decode (repro.obs
`engine/decode_dispatch` spans, api/engine.py Engine.decode)."""
from bench import spans


def read(rec):
    if rec.get("kind") != "serve":
        return None
    spent = spans.durations(rec, "engine", "decode_dispatch")
    if not spent:
        return None
    return 1e3 * sum(spent) / len(spent)
