"""From the profiler's trace of a window to numbers: device busy and idle
time (the union of the intervals in which an operation ran on a device),
device time by operation and by program, the host annotations that the
harness puts around its calls into each layer, and the `breakdown` the
result line carries (the device operations that took most time, and the
idle time labelled by what the host was doing meanwhile).

Reads the `.xplane.pb` that jax.profiler writes, through
jax.profiler.ProfileData. Device planes are named `/device:TPU:<n>`; on
each, the line `XLA Ops` holds one event per operation run, named by its
HLO text (`%copy.531 = f32[28,81,8,128,128]{...} copy(...)`), and
`XLA Modules` one per program run (`jit_dec_fn(<fingerprint>)`). A Pallas
kernel is a `custom-call` whose instruction carries the name of the jitted
step that calls it (`%dec_fn.12 = ... custom-call(...)`): the program gives
its kernels no name of their own yet. Host annotations are events of the
`/host:CPU` plane whose names are in ANNOTATIONS.
"""
from __future__ import annotations

import glob
import os
import re

#: the harness's own host annotations (bench/drivers/*.py)
ANNOTATIONS = ("engine.prefill_into", "engine.decode", "logits_to_host",
               "wave_step", "ps.pull", "ps.push_wave", "loader.next")

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def stable(name: str) -> str:
    """A program's or an operation's name without the numeric suffixes and
    fingerprints XLA adds: `jit_dec_fn(123)` -> `jit_dec_fn`,
    `%copy.531 = f32[2,3]{1,0} copy(...)` -> `copy`."""
    head = name.split(" = ")[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", re.sub(r"\(-?\d+\)$", "", head))


def op_label(name: str) -> str:
    """An operation's stable name with its result type, for the breakdown:
    `copy f32[28,81,8,128,128]`."""
    parts = name.split(" = ", 1)
    if len(parts) == 1:
        return stable(name)
    return f"{stable(name)} {parts[1].split('{')[0].split(' ')[0]}"


def load(path: str) -> dict:
    """Device op, module and host-annotation events of one .xplane.pb, with
    times in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            d = dev.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    d[key].append((e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9,
                                   e.name))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in ANNOTATIONS:
                        host.append((e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9,
                                     e.name))
    return {"devices": dev, "host": host}


def reduce(trace: dict, window_s: float) -> dict:
    """busy_s (averaged over the devices that ran anything), op and module
    seconds by stable name, and the breakdown."""
    devs = [d for d in trace["devices"].values() if d["ops"]]
    ops, calls, modules, busy, gaps = {}, {}, {}, [], {}
    for d in devs:
        merged = union((a, b) for a, b, _ in d["ops"])
        busy.append(sum(b - a for a, b in merged))
        for a, b, n in d["ops"]:
            label = op_label(n)
            ops[label] = ops.get(label, 0.0) + (b - a)
            if " custom-call(" in n:
                calls[stable(n)] = calls.get(stable(n), 0.0) + (b - a)
        for a, b, n in d["modules"]:
            modules[stable(n)] = modules.get(stable(n), 0.0) + (b - a)
        idle = [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])]
        for (a, b), label in zip(idle, host_labels(trace["host"], idle)):
            gaps[label] = gaps.get(label, 0.0) + (b - a)
    busy_s = sum(busy) / len(busy) if busy else 0.0
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy_s, "window_s": window_s, "ops": ops,
            "custom_calls": calls, "modules": modules, "devices": len(devs),
            "breakdown": {"device_ops": [[n, s] for n, s in top],
                          "idle_gaps": [[n, s] for n, s in idle]}}


def host_labels(host, gaps):
    """For each idle gap (sorted), the annotation that covers most of it,
    or `host (other)` where none does."""
    host = sorted(host)
    out, active, i = [], [], 0
    for a, b in gaps:
        while i < len(host) and host[i][0] < b:
            active.append(host[i])
            i += 1
        active = [h for h in active if h[1] > a]
        best, label = 0.0, "host (other)"
        for s, e, n in active:
            o = min(e, b) - max(s, a)
            if o > best:
                best, label = o, n
        out.append(label)
    return out


def kernel_seconds(red: dict, name: str) -> float:
    """Device seconds of the custom calls (Pallas kernels) named `name`."""
    return red["custom_calls"].get(name, 0.0)


def reduce_dir(log_dir: str, rec: dict) -> dict:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    window_s = rec.get("t1", 0.0) - rec.get("t0", 0.0)
    return reduce(load(max(paths, key=os.path.getmtime)), window_s)
