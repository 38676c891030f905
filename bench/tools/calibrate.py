"""Readings that a cell's limits are set from, in one process on the chip:
the program's numbers over many seeds, and the control's (the reference at
fp8 operands put in the program's place) and, for training, the half-batch
fault's over a few, each at the cell's own size and load with a short
window. The benchmark's own runs never run the control.

    python bench/tools/calibrate.py <workload> --seeds 12 --control-seeds 3 \
        --seconds 20 [--out bench-out/calibrate]

Writes one JSON line per seed to <out>/<workload>.jsonl and prints it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import common, run  # noqa: E402

FIRST_SEED = 3_000_000_000      # above 2**31, so a seed's high bits count


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--first-seed", type=int, default=FIRST_SEED)
    ap.add_argument("--out", default="bench-out/calibrate")
    a = ap.parse_args()
    os.makedirs(a.out, exist_ok=True)
    spec = common.benchmark_spec()
    files = common.cell_files(a.workload, spec)
    train = files[2]["driver"] == "train_wsp"
    faults = ("control", "half_batch") if train else ("control",)
    kw = {}
    if train:
        # one compiled wave step for every seed: the same program each time
        common.program()
        from repro.core import wave
        from repro.optim import make_optimizer
        traffic = files[2]
        arch = common.arch_for(files[1])
        kw["wave_step"] = wave.build_local_wave_step(
            arch, traffic["microbatches"],
            make_optimizer(traffic["optimizer"], traffic["lr"]))
    with open(os.path.join(a.out, a.workload + ".jsonl"), "a") as f:
        for i in range(a.seeds):
            seed = a.first_seed + 7919 * i
            readings = ("program",) + (faults if i < a.control_seeds else ())
            res = run.run_cell(a.workload, seed, a.seconds, False, spec=spec,
                               files=files, readings=readings, **kw)
            rec = res["_record"]
            line = {"workload": a.workload, "seed": seed,
                    "program": rec["check"], "faults": rec["readings"],
                    "metrics": rec["metrics"],
                    "memory_peak_bytes": rec["device"]["memory_peak_bytes"],
                    "compiles_in_window": rec["compiles_in_window"],
                    "losses": rec.get("check_losses"),
                    "extra": rec.get("extra_lines")}
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()
            del res, rec
            gc.collect()


if __name__ == "__main__":
    main()
