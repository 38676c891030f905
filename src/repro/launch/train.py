"""End-to-end training driver — a thin CLI over the repro.api layer.

Both modes build a declarative `repro.api.Plan` and run it through the same
`Engine`:
  --mode spmd    one jitted pipelined wave step over a (data, stage, tp) mesh
                 (WSP D=0; the production path — on CPU use a small mesh via
                 --devices, which must be set before jax initializes, so this
                 mode re-execs itself with XLA_FLAGS when needed)
  --mode wsp     threaded multi-VW WSP runtime with the parameter server
                 (true async D>=0, stragglers, checkpoint/restart, elastic)

Example (CPU, reduced model, a few hundred steps):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --mode wsp \
      --reduced --waves 50 --num-vw 4 --D 2
"""
from __future__ import annotations

import argparse
import os
import sys


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--mode", choices=("spmd", "wsp"), default="wsp")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--waves", type=int, default=50)
    ap.add_argument("--num-vw", type=int, default=4)
    ap.add_argument("--D", type=int, default=0)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compression", type=float, default=None)
    ap.add_argument("--codec", default=None,
                    help="gradient codec: topk:<ratio> | int8 | none")
    ap.add_argument("--topology", default=None,
                    help="network model spec, or 'list' to print every "
                         "accepted spec and exit (default: zero-latency)")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="scale modeled network delays before sleeping")
    ap.add_argument("--overlap", action="store_true",
                    help="overlap communication with compute: wsp mode pushes "
                         "wave deltas asynchronously (next wave's forward "
                         "starts while the push is in flight); spmd mode uses "
                         "the software-pipelined (skewed) schedule so the "
                         "boundary ppermute runs concurrently with stage "
                         "compute")
    ap.add_argument("--pull-every", type=int, default=1,
                    help="wsp mode: pull w_global every k waves (local delta "
                         "updates in between; k>1 lets async pushes overlap)")
    ap.add_argument("--speeds", default=None,
                    help="comma-separated per-VW slowdowns (s/wave)")
    ap.add_argument("--devices", type=int, default=0,
                    help="spmd mode: fake host device count (data*stage*tp)")
    ap.add_argument("--mesh", default="2,2,2",
                    help="spmd mode: data,stage,tp")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace-event JSON (Perfetto-"
                         "loadable) of the run, with the metrics snapshot "
                         "embedded; inspect with python -m repro.obs.summary")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="wsp mode: inject the seeded random fault scenario "
                         "FaultPlan.sample_train(SEED) — a worker crash, a "
                         "link outage on a push path, a slowdown onset and a "
                         "PS stall — with eviction + rejoin recovery "
                         "enabled; prints the run's fault digest")
    return ap


def main(argv=None):
    a = build_parser().parse_args(argv)

    if a.topology == "list":
        from repro.dist.topology import topology_help
        print("accepted --topology specs:")
        print(topology_help())
        return

    # the re-exec trick only makes sense for a real CLI invocation: sys.argv
    # is this process's own command line. A programmatic caller passing argv
    # must set XLA_FLAGS itself (the Engine's device check says how).
    if a.mode == "spmd" and a.devices and argv is None \
            and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={a.devices}"
        os.execv(sys.executable, [sys.executable] + sys.argv)

    import jax
    import numpy as np

    from repro.api import ClusterSpec, Engine, PartitionSpec, Plan, \
        RunSpec, WSP
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs import ARCHS, reduced as make_reduced
    from repro.obs import NULL_TRACER, Tracer

    tracer = Tracer() if a.trace else NULL_TRACER

    cfg = ARCHS[a.arch]
    if a.reduced:
        dm, st, tp = a.d_model, 2, 1
        heads = max(1, min(cfg.num_heads, 4)) if cfg.num_heads else 0
        cfg = make_reduced(cfg, d_model=dm, d_ff=2 * dm, num_layers=a.layers,
                           vocab_size=256, stages=st, tp=tp,
                           num_heads=heads,
                           num_kv_heads=max(1, heads // 2) if heads else 0,
                           head_dim=dm // heads if heads else 0)
    print(f"arch={cfg.name} params={cfg.param_count():,} (analytic)")

    if a.mode == "wsp":
        if a.overlap and a.pull_every == 1:
            print("note: --overlap with --pull-every 1 serializes every push "
                  "behind the following pull (each wave starts from freshly "
                  "pulled weights); use --pull-every > 1 to actually hide "
                  "push latency", file=sys.stderr)
        speeds = ([float(s) for s in a.speeds.split(",")]
                  if a.speeds else None)
        fault_kwargs = {}
        if a.chaos is not None:
            from repro.api import FaultPlan, FaultPolicy
            faults = FaultPlan.sample_train(a.chaos, num_vw=a.num_vw,
                                            max_waves=a.waves)
            fault_kwargs = dict(
                faults=faults,
                fault_policy=FaultPolicy(evict_lag=1, rejoin_after_waves=1,
                                         allow_degraded=True))
            print(f"chaos: {faults.describe()}")
        plan = Plan(
            arch=cfg,
            cluster=ClusterSpec(num_vw=a.num_vw, topology=a.topology,
                                speeds=speeds, time_scale=a.time_scale),
            sync=WSP(D=a.D, pull_every=a.pull_every, async_push=a.overlap),
            run=RunSpec(max_waves=a.waves, batch=a.batch, seq=a.seq,
                        optimizer=a.optimizer, lr=a.lr,
                        compression_ratio=a.compression, codec=a.codec,
                        ckpt_dir=a.ckpt_dir, ckpt_every=a.ckpt_every,
                        resume=a.resume),
            **fault_kwargs)
        eng = Engine(plan, tracer=tracer)
        rep = eng.fit()
        if a.chaos is not None:
            print(f"faults: {rep.fault_digest()}")
        if a.trace:
            print(f"trace: {tracer.export(a.trace)}")
        xs, ys = rep.loss_curve()
        print(f"waves={rep.waves} wall={rep.wall_s:.1f}s "
              f"first_loss={ys[0]:.4f} last_loss={np.mean(ys[-5:]):.4f}")
        if a.overlap:
            print(f"overlap: hidden={rep.overlap_seconds:.2f}s "
                  f"blocked={rep.push_wait_seconds:.2f}s")
        print(f"pushed={rep.bytes_pushed/1e6:.1f}MB wire="
              f"{rep.bytes_wire/1e6:.1f}MB waits={ {k: round(v,2) for k, v in rep.wait_seconds.items()} }")
        if eng.topology is not None:
            by_link = rep.comm.get("bytes_by_link", {})
            print(f"network: modeled={rep.comm_seconds:.2f}s "
                  f"bytes_by_link={ {k: f'{v/1e6:.1f}MB' for k, v in by_link.items()} }")
        return

    # spmd mode
    if a.chaos is not None:
        raise SystemExit("--chaos needs the threaded WSP runtime; "
                         "use --mode wsp")
    if a.topology or a.codec or a.compression:
        print("warning: --topology/--codec/--compression only apply to "
              "--mode wsp; ignored in spmd mode", file=sys.stderr)
    dsz, ssz, tsz = (int(x) for x in a.mesh.split(","))
    plan = Plan(
        arch=cfg,
        partition=PartitionSpec(data=dsz, stages=ssz, tp=tsz),
        sync=WSP(D=0),
        run=RunSpec(backend="spmd", max_waves=a.waves, batch=a.batch,
                    seq=a.seq, optimizer=a.optimizer, lr=a.lr,
                    overlap=a.overlap, resume=a.resume,
                    ckpt_dir=a.ckpt_dir,
                    ckpt_every=a.ckpt_every if a.ckpt_dir else 0))
    eng = Engine(plan, tracer=tracer)
    n_dev = len(jax.devices())
    print(f"mesh=({dsz},{ssz},{tsz}) devices={n_dev}")

    def log(w, loss, dt):
        if w % 5 == 0 or w == a.waves - 1:
            print(f"wave {w:4d} loss={loss:.4f} ({dt:.2f}s)")

    eng.fit(callback=log)
    if a.trace:
        print(f"trace: {tracer.export(a.trace)}")


if __name__ == "__main__":
    main()
