"""Decode-batch occupancy: tokens decoded over decode steps x max_batch,
in the window (the Scheduler's slot_steps / (decode_steps x max_batch))."""


def read(rec):
    w = rec.get("window")
    if not w or not w["decode_calls"]:
        return None
    live = sum(len(slots) for _, _, slots, _ in w["decode_calls"])
    return 100.0 * live / (len(w["decode_calls"]) * rec["max_batch"])
