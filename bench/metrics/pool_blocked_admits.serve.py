"""Admissions the page pool refused, per 100 requests admitted, in the
window: the Scheduler's `sched/refuse` instants (ServeReport.admit_blocked)
over the rows of its prefill groups. Nothing to read where the cell has no
pool (every layer windowed)."""


def read(rec):
    w = rec.get("window")
    if not w or not rec.get("page_pool"):
        return None
    admitted = sum(len(slots) for _, _, slots, _ in w["prefill_calls"])
    refused = sum(1 for e in rec["events"]
                  if e[0] == "i" and e[1] == "sched" and e[2] == "refuse")
    if not admitted:
        return None
    return 100.0 * refused / admitted
