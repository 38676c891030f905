"""Run one benchmark cell on the chip this process holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout. The cell is looked up by name in
BENCHMARK.json; its configuration, traffic mix, sizing and limits come
from the files named after them under bench/, and the traffic names the
driver (bench/drivers/<driver>.py) that runs it. With --trace 0 the result
carries the cell's end-to-end metrics; with --trace 1 the window is
profiled and the result carries the per-layer metrics, each read by
bench/metrics/<metric>.py from the run's record and the trace.

The last line of standard output is one JSON object (correct, attempted,
failed, metrics, device[, breakdown], checks). Exits non-zero, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for, or
where the program is not in the checkout.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import common  # noqa: E402


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, workload: str):
    """(end-to-end, per-layer) metric entries this cell reports."""
    def here(m, default):
        return workload in m["workloads"] if "workloads" in m else default

    e2e = [m for m in spec["end_to_end"] if here(m, True)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if here(m, m["moves"] in names)]
    return e2e, layer


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chips: bool = True, spec=None, files=None,
             **driver_kw) -> dict:
    """Everything a run does between the argument parse and the print.
    Tests call it with `require_chips=False` and their own files."""
    spec = spec if spec is not None else common.benchmark_spec()
    w, cfg, traffic, cell = files or common.cell_files(workload, spec)
    common.program()
    import jax
    devices = jax.devices()
    if require_chips:
        if devices[0].platform != "tpu":
            raise SystemExit(f"bench: needs a TPU; JAX found "
                             f"{devices[0].platform}")
        if len(devices) < w["chips"]:
            raise SystemExit(f"bench: the cell asks for {w['chips']} chips, "
                             f"JAX sees {len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # keep every program, however quick to compile, so that a second run
    # of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = common.CompileCounter()
    driver = load_module(os.path.join(common.BENCH, "drivers",
                                      traffic["driver"] + ".py"))
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        rec = driver.run(cfg, traffic, cell, seed=seed, seconds=seconds,
                         trace=trace, t_start=T_START, counter=counter,
                         tracer_dir=tdir, **driver_kw)
        rec["workload"] = workload
        rec["compile_cache"] = cache_dir
        rec["compiles_total"] = counter.compiles
        rec["cache_hits"] = counter.cache_hits
        e2e, layer = cell_metrics(spec, workload)
        out = {}
        if trace:
            from bench import trace_reduce
            rec["trace"] = trace_reduce.reduce_dir(tdir, rec)
            for m in layer:
                value = load_module(os.path.join(
                    common.BENCH, "metrics", m["name"] + ".py")).read(rec)
                if value is not None:
                    out[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in e2e:
                out[m["name"]] = {"value": rec["metrics"][m["name"]],
                                  "unit": m["unit"]}
    limits = cell.get("limits", {})
    checks = {k: {"value": rec["check"][k], "limit": lim}
              for k, lim in limits.items()}
    correct = (bool(checks) and rec["attempted"] > 0 and rec["failed"] == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": out,
              "device": dict(rec["device"])}
    if trace:
        t = rec["trace"]
        result["device"].update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = t["breakdown"]
    result["checks"] = checks
    result["_record"] = rec
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    except ImportError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    rec = result.pop("_record")
    print(f"bench: setup_s={rec['setup_s']:.3f} compiles_total="
          f"{rec['compiles_total']} cache_hits={rec['cache_hits']} "
          f"compiles_in_window={rec['compiles_in_window']} "
          f"cache={rec['compile_cache']}", file=sys.stderr)
    for k, v in rec.get("extra_lines", {}).items():
        print(f"bench: {k}={v}", file=sys.stderr)
    for k, v in rec["check"].items():
        if k not in result["checks"]:
            print(f"bench: reading {k}={v}", file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
