"""Run a cell several times, one process per run, and summarise the spread
of each metric, as the bounds in BENCHMARK.json are set from.

    python bench/tools/measure.py <workload> --seeds S1 S2 ... --sets 2 \
        [--trace 0|1] [--seconds N] [--out bench-out/measure]

Each set runs every seed once, in order; the sets use the same seeds. The
parent never imports JAX (the child that runs the cell holds the chip).
Writes <out>/<workload>.jsonl (one line per run: set, seed, result, the
stderr's last lines, wall time) and prints, per set and metric, the median,
the quartiles (statistics.quantiles, n=4) and the spread (IQR / median).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values):
    if len(values) < 2 or not statistics.median(values):
        return None
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return {"median": statistics.median(values), "q1": q1, "q3": q3,
            "spread": (q3 - q1) / statistics.median(values)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=0)
    ap.add_argument("--out", default="bench-out/measure")
    a = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = a.seconds or json.load(f)["run_seconds"]
    os.makedirs(a.out, exist_ok=True)
    path = os.path.join(a.out, a.workload + ".jsonl")
    runs = []
    for s in range(a.sets):
        for seed in a.seeds:
            t0 = time.monotonic()
            p = subprocess.run(
                [sys.executable, "bench/run.py", "--workload", a.workload,
                 "--seed", str(seed), "--seconds", str(seconds),
                 "--trace", str(a.trace)], cwd=ROOT, capture_output=True,
                text=True)
            wall = time.monotonic() - t0
            lines = p.stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1]) if lines else None
            except json.JSONDecodeError:
                result = None
            run = {"set": s, "seed": seed, "rc": p.returncode,
                   "wall_s": wall, "result": result,
                   "stderr_tail": p.stderr[-3000:]}
            runs.append(run)
            with open(path, "a") as f:
                f.write(json.dumps(run) + "\n")
            short = {k: v["value"] for k, v in
                     (result or {}).get("metrics", {}).items()}
            print(json.dumps({"set": s, "seed": seed, "rc": p.returncode,
                              "wall_s": round(wall, 1),
                              "correct": (result or {}).get("correct"),
                              "metrics": short,
                              "checks": (result or {}).get("checks")}),
                  flush=True)
            if p.returncode != 0:
                print(p.stderr[-3000:], flush=True)
    for s in range(a.sets):
        got = [r["result"] for r in runs if r["set"] == s and r["result"]]
        names = sorted({k for g in got for k in g["metrics"]})
        for n in names:
            vals = [g["metrics"][n]["value"] for g in got
                    if n in g["metrics"]]
            print(json.dumps({"set": s, "metric": n, "values": vals,
                              "summary": spread(vals)}), flush=True)


if __name__ == "__main__":
    main()
