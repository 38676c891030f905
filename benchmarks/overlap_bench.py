"""Comm/compute overlap benchmark — the three overlap layers, measured.

Sweeps overlap on/off and writes machine-readable ``BENCH_overlap.json``:

  runtime     threaded WSP fleet, blocking vs async push, per topology
              preset x model: wall clock, modeled comm, hidden (overlapped)
              comm. The simulated network is scaled so one wave's push costs
              about one wave's compute on the hetero preset's inter-node
              link — the regime where async push matters (comm ~ compute,
              max(c,m) vs c+m). The all-NVLink `single` preset is the
              control: with ~zero comm to hide, async push only pays its
              outbox thread-handoff overhead, so its reduction hovers
              around (or slightly below) zero — only the cross-node presets
              are expected to win.
  partitioner analytic min-max partition with real stage-boundary links,
              serial vs overlap-aware stage_time: minmax stage seconds and
              1F1B throughput.
  spmd        the skewed (software-pipelined) wave schedule vs the oracle
              schedule: loss/param identity, via the canonical subprocess
              harness (tests/pipeline_equiv_main.py, mode 'overlap'), run
              first, before this process initializes a JAX backend.

  PYTHONPATH=src python benchmarks/overlap_bench.py [--tiny] [--out PATH]

--tiny is the CI smoke configuration (fewer waves, fewer cells).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

import jax
import numpy as np

from repro.api import ClusterSpec, Engine, Plan, RunSpec, WSP
from repro.configs import ARCHS, reduced
from repro.core import wave
from repro.core.partition import (PAPER_GPUS, layer_costs, partition_minmax,
                                  pipeline_throughput)
from repro.dist.topology import ETH_1G, ETH_10G, make_topology, stage_links
from repro.models import lm
from repro.optim import make_optimizer

NUM_VW = 2
D = 2
PULL_EVERY = 4
BATCH, SEQ = 4, 32
# simulated per-wave compute (s) added to every VW: real compute on the tiny
# CPU model is ~ms, below thread-scheduling noise; this pins the
# compute:comm ratio near 1 where overlap matters most
SLOWDOWN = 0.05


def tiny_cfg(name):
    c = ARCHS[name]
    return reduced(c, num_layers=2, d_model=32, d_ff=64, vocab_size=256,
                   num_heads=2 if c.num_heads else 0,
                   num_kv_heads=2 if c.num_heads else 0,
                   head_dim=16 if c.num_heads else 0, num_microbatches=2)


def _setup(cfg):
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    opt = make_optimizer("sgd", 0.3)
    step = wave.build_local_wave_step(cfg, cfg.num_microbatches, opt)
    return params, opt, step


def _measure_wave_seconds(params, opt, step, reps=3):
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, (BATCH, SEQ)).astype(np.int32)
    y = rng.integers(0, 256, (BATCH, SEQ)).astype(np.int32)
    st = opt.init(params)
    step(params, st, x, y)                         # warm the jit cache
    ts = []
    for _ in range(reps):
        t0 = time.monotonic()
        step(params, st, x, y)
        ts.append(time.monotonic() - t0)
    return float(np.median(ts))


def runtime_sweep(arch_names, topo_specs, waves):
    rows = []
    for name in arch_names:
        cfg = tiny_cfg(name)
        params, opt, step = _setup(cfg)
        t_comp = _measure_wave_seconds(params, opt, step) + SLOWDOWN
        push_bytes = sum(np.asarray(l).astype(np.float32).nbytes
                         for l in jax.tree.leaves(params))
        # one push ~ one wave of compute on the hetero inter-node link; the
        # same time_scale is reused for every preset of this model so fast
        # links stay fast
        ref = make_topology("hetero", NUM_VW)
        ref_cost = max(ref.p2p_cost(f"vw{i}", "ps", push_bytes)
                       for i in range(NUM_VW))
        time_scale = t_comp / ref_cost if ref_cost > 0 else 0.0
        base = Plan(cluster=ClusterSpec(num_vw=NUM_VW),
                    sync=WSP(D=D),
                    run=RunSpec(max_waves=2, batch=BATCH, seq=SEQ,
                                vocab=cfg.vocab_size))
        # throwaway run: everything (jit cache, worker threads, loaders)
        # warm before any timed cell
        Engine(base, params=params, wave_step=step, optimizer=opt).fit()
        for spec in topo_specs:
            cell = {"arch": name, "topology": spec,
                    "time_scale": time_scale,
                    "wave_compute_s": t_comp, "push_bytes": int(push_bytes)}
            for mode, async_push in (("blocking", False), ("async", True)):
                plan = base.replace(
                    cluster=ClusterSpec(num_vw=NUM_VW,
                                        topology=make_topology(spec, NUM_VW),
                                        speeds=[SLOWDOWN] * NUM_VW,
                                        time_scale=time_scale),
                    sync=WSP(D=D, pull_every=PULL_EVERY,
                             async_push=async_push),
                    run__max_waves=waves)
                rep = Engine(plan, params=params, wave_step=step,
                             optimizer=opt).fit()
                cell[mode] = {
                    "wall_s": rep.wall_s, "waves": rep.waves,
                    "comm_seconds": rep.comm_seconds,
                    "overlap_seconds": rep.overlap_seconds,
                    "push_wait_seconds": rep.push_wait_seconds,
                }
            cell["reduction_pct"] = 100.0 * (
                1.0 - cell["async"]["wall_s"] / cell["blocking"]["wall_s"])
            print(f"runtime {name:14s} {spec:8s} "
                  f"blocking={cell['blocking']['wall_s']:.2f}s "
                  f"async={cell['async']['wall_s']:.2f}s "
                  f"hidden={cell['async']['overlap_seconds']:.2f}s "
                  f"reduction={cell['reduction_pct']:.1f}%")
            rows.append(cell)
    return rows


def partitioner_sweep(arch_names, nm=4):
    """HD-style heterogeneous 4-stage fleets with Ethernet at the
    type-change boundaries (10 GbE and whimpy 1 GbE): overlap-aware
    stage_time vs serial."""
    rows = []
    fleets = {"VVQQ": [PAPER_GPUS["V"]] * 2 + [PAPER_GPUS["Q"]] * 2,
              "RRGG": [PAPER_GPUS["R"]] * 2 + [PAPER_GPUS["G"]] * 2}
    inters = {"eth10": ETH_10G, "eth1": ETH_1G}
    for name in arch_names:
        cfg = ARCHS[name]
        fl, pb, ab = layer_costs(cfg, 4096, nm * 4096)
        for (fname, devs), (iname, inter) in (
                (f, i) for f in fleets.items() for i in inters.items()):
            links = stage_links(devs, inter)
            cell = {"arch": name, "fleet": fname, "inter": iname, "nm": nm,
                    "links": [l.name for l in links]}
            for mode, overlap in (("serial", False), ("overlap", True)):
                bounds, times, ok = partition_minmax(
                    fl, ab, pb, devs, nm, links=links, overlap=overlap)
                cell[mode] = {
                    "feasible": bool(ok),
                    "bounds": bounds if ok else None,
                    "minmax_stage_s": float(max(times)) if ok else None,
                    "throughput_mb_s":
                        pipeline_throughput(times, nm) if ok else 0.0,
                }
            if cell["serial"]["feasible"] and cell["overlap"]["feasible"]:
                cell["speedup"] = (cell["overlap"]["throughput_mb_s"]
                                   / cell["serial"]["throughput_mb_s"])
                cell["cuts_moved"] = (cell["serial"]["bounds"]
                                      != cell["overlap"]["bounds"])
            def _fmt(v):
                return f"{v:.4f}s" if v is not None else "infeasible"
            print(f"partition {name:14s} {fname}/{iname} "
                  f"serial={_fmt(cell['serial']['minmax_stage_s'])} "
                  f"overlap={_fmt(cell['overlap']['minmax_stage_s'])} "
                  f"speedup={cell.get('speedup', 0):.3f}x "
                  f"cuts_moved={cell.get('cuts_moved')}")
            rows.append(cell)
    return rows


def spmd_identity(arch_name):
    """Skewed schedule vs oracle schedule on a fake multi-device mesh —
    delegated to the canonical equivalence harness
    (tests/pipeline_equiv_main.py, mode 'overlap'), the same subprocess
    tests/test_system.py drives, so there is exactly one implementation of
    the identity check."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tests",
                                      "pipeline_equiv_main.py"),
         arch_name, "overlap"],
        capture_output=True, text=True, timeout=1200, env=env)
    m = re.search(r"overlap_loss_diff=(\S+) overlap_param_diff=(\S+)",
                  r.stdout)
    out = {"arch": arch_name,
           "loss_identical": r.returncode == 0 and m is not None,
           "loss_diff": float(m.group(1)) if m else None,
           "param_diff": float(m.group(2)) if m else None}
    if r.returncode != 0:
        out["error"] = (r.stdout + r.stderr)[-500:]
    print(f"spmd {arch_name}: loss_identical={out['loss_identical']} "
          f"loss_diff={out['loss_diff']} param_diff={out['param_diff']}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke configuration")
    ap.add_argument("--out", default="BENCH_overlap.json")
    a = ap.parse_args()
    if a.tiny:
        archs, topos, waves = ["qwen3-0.6b"], ["single", "hetero"], 8
        part_archs = ["qwen3-0.6b"]
    else:
        archs, topos, waves = (["qwen3-0.6b", "gemma3-1b"],
                               ["single", "2node", "hetero"], 16)
        part_archs = ["qwen3-0.6b", "gemma3-1b", "granite-moe-1b-a400m"]
    # the identity check runs in a child process; start it before this
    # process touches a JAX backend, or on an accelerator host the parent
    # would hold the device the child needs
    spmd = spmd_identity(archs[0])
    doc = {
        "meta": {"mode": "tiny" if a.tiny else "full", "num_vw": NUM_VW,
                 "D": D, "pull_every": PULL_EVERY, "waves": waves,
                 "time_scale_policy":
                     "one push ~ one wave compute on hetero inter link"},
        "runtime": runtime_sweep(archs, topos, waves),
        "partitioner": partitioner_sweep(part_archs),
        "spmd": spmd,
    }
    with open(a.out, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {a.out}")
    het = [r for r in doc["runtime"] if r["topology"] == "hetero"]
    for r in het:
        print(f"hetero {r['arch']}: async push cuts simulated wall clock by "
              f"{r['reduction_pct']:.1f}%")


if __name__ == "__main__":
    main()
