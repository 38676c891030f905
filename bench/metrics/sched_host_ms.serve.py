"""The Scheduler's own host work per pass of its loop: each `sched/step`
span less the spans opened directly inside it (`sched/prefill_group`,
`sched/decode_step`), which wait on the device. The rest (token picks,
retirement, admission planning) dispatches nothing (repro.obs spans,
api/serving.py Scheduler.run)."""
import bisect


def read(rec):
    if rec.get("kind") != "serve":
        return None
    ev = [e for e in rec["events"] if e[0] == "X"]
    steps = sorted((t0, t0 + dur) for _, track, name, t0, dur, _ in ev
                   if (track, name) == ("sched", "step"))
    if not steps:
        return None
    starts = [a for a, _ in steps]
    inner = 0.0
    for _, _, _, t0, dur, args in ev:
        if args.get("parent") == "sched/step":
            i = bisect.bisect_right(starts, t0) - 1
            if i >= 0 and t0 + dur <= steps[i][1]:
                inner += dur
    return 1e3 * (sum(b - a for a, b in steps) - inner) / len(steps)
