"""Set a cell's limits from its calibration readings (bench/tools/
calibrate.py), by the rule the benchmark keeps:

- lower reading: the largest the program gives over all seeds read;
- upper reading: the smallest the control gives (the reference at fp8 put
  in the program's place), where that is 3x the lower or more; for a
  training cell also the smallest the half-batch fault gives where that is
  10x the lower or more, and, for the two leaf measures, 1 (a step that
  leaves the state unchanged reads 1 by their measure) where that is 3x;
- limit: above the lower and below the upper, with more room above the
  lower, lower^(1/3) * upper^(2/3) (upper / 3 where the lower reads 0).

A number with no upper reading gets no limit and is reported. The tool
also checks that the control, and for training the half-batch fault, fail
at least one number on every seed read.

    python bench/tools/set_limits.py <calibration.jsonl> [--write]

--write puts the limits into bench/cells/<workload>.json.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import common  # noqa: E402

NUMBERS = {"serve_backlog": ("logit_err",),
           "train_wsp": ("loss1_gap", "grad_gap", "change_gap_median")}
LEAF = ("grad_gap", "change_gap_median")


def limits(lines: list, driver: str) -> dict:
    out = {}
    for k in NUMBERS[driver]:
        prog = [ln["program"][k] for ln in lines]
        lower = max(prog)
        cands = {}
        ctl = [ln["faults"]["control"][k] for ln in lines
               if "control" in ln["faults"]]
        if ctl and min(ctl) > 0 and min(ctl) >= 3 * lower:
            cands["control"] = min(ctl)
        half = [ln["faults"]["half_batch"][k] for ln in lines
                if "half_batch" in ln["faults"]]
        if half and min(half) > 0 and min(half) >= 10 * lower:
            cands["half_batch"] = min(half)
        if driver == "train_wsp" and k in LEAF and 1.0 >= 3 * lower:
            cands["unchanged_state"] = 1.0
        entry = {"lower": lower, "seeds": len(prog), "control": ctl,
                 "half_batch": half, "upper_from": cands}
        if cands:
            upper = min(cands.values())
            entry["upper"] = upper
            entry["limit"] = (upper / 3 if lower <= 0 else
                              math.exp(math.log(lower) / 3
                                       + 2 * math.log(upper) / 3))
        out[k] = entry
    return out


def fails_one(lines: list, which: str, lim: dict) -> list:
    """Per seed read with fault `which`: does it fail a limited number?"""
    return [any(ln["faults"][which][k] > e["limit"]
                for k, e in lim.items() if "limit" in e)
            for ln in lines if which in ln["faults"]]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("readings")
    ap.add_argument("--write", action="store_true")
    a = ap.parse_args()
    with open(a.readings) as f:
        lines = [json.loads(x) for x in f if x.strip()]
    workload = lines[0]["workload"]
    w, cfg, traffic, cell = common.cell_files(workload,
                                              common.benchmark_spec())
    lim = limits(lines, traffic["driver"])
    report = {"workload": workload, "numbers": lim,
              "control_fails_one": fails_one(lines, "control", lim),
              "half_batch_fails_one": fails_one(lines, "half_batch", lim)}
    print(json.dumps(report, indent=1))
    if a.write:
        cell["limits"] = {k: e["limit"] for k, e in lim.items()
                          if "limit" in e}
        path = os.path.join(common.BENCH, "cells", workload + ".json")
        with open(path, "w") as f:
            json.dump(cell, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
