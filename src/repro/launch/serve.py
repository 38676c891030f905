"""Serving driver — a thin CLI over the repro.api serve surface.

Builds a serve-mode Plan (Plan.serve = ServeSpec) and runs it through the
same Engine the training drivers use:

  --backend threads   the non-pipelined forward_ref cache path (CPU oracle)
  --backend spmd      the pipelined prefill/decode steps on a
                      (1, stages, tp) mesh (re-execs with XLA_FLAGS when
                      --devices asks for fake CPU devices)

By default one aligned batch runs through Engine.generate(); --requests N
instead pushes N FIFO requests through the continuous-batching scheduler
(repro.api.serving) and prints per-request latency and slot occupancy.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --reduced \
      --batch 4 --prompt-len 24 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 8 --batch 2 --gen 8
  python -m repro.launch.serve --arch qwen3-0.6b --full --kernel-backend tpu \
      --requests 16 --batch 8 --prompt-len 512 --gen 32 --page-size 128
"""
from __future__ import annotations

import argparse
import os
import sys


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    width = ap.add_mutually_exclusive_group()
    width.add_argument("--reduced", dest="reduced", action="store_true",
                       default=True,
                       help="serve the arch's reduced() CPU config "
                            "(default)")
    width.add_argument("--full", dest="reduced", action="store_false",
                       help="serve the arch at its published widths")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode batch slots (ServeSpec.max_batch)")
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--backend", choices=("threads", "spmd"),
                    default="threads")
    ap.add_argument("--kernel-backend", choices=("ref", "interpret", "tpu"),
                    default="ref",
                    help="hot-path attention/SSM implementation "
                         "(ServeSpec.kernel_backend): 'ref' = jnp, "
                         "'interpret' = Pallas kernels executed in Python "
                         "(CPU parity), 'tpu' = compiled Mosaic kernels")
    ap.add_argument("--mesh", default="1,2,1",
                    help="spmd backend: data,stages,tp (data must be 1)")
    ap.add_argument("--devices", type=int, default=0,
                    help="spmd backend: fake host device count")
    ap.add_argument("--requests", type=int, default=0,
                    help="serve N requests through the continuous-batching "
                         "scheduler instead of one aligned batch")
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV page tokens (ServeSpec.page_size; 0 = "
                         "contiguous degenerate, one page per slot)")
    ap.add_argument("--max-pages", type=int, default=0,
                    help="KV page pool size (ServeSpec.max_pages; 0 = "
                         "worst case batch * pages-per-slot)")
    ap.add_argument("--share-prefix", action="store_true",
                    help="map each request's longest indexed prompt prefix "
                         "onto refcounted shared pages (ServeSpec."
                         "share_prefix); the synthetic requests draw from "
                         "a small prompt pool so prefixes actually repeat")
    ap.add_argument("--evict", action="store_true",
                    help="reclaim cold indexed pages LRU-first under pool "
                         "pressure (ServeSpec.evict; needs --share-prefix)")
    ap.add_argument("--preempt", action="store_true",
                    help="under pool pressure, preempt an in-flight "
                         "request (fewest tokens generated, or most "
                         "deadline slack) and replay it instead of "
                         "refusing admission (ServeSpec.preempt)")
    ap.add_argument("--policy", choices=("fifo", "deadline"),
                    default="fifo",
                    help="scheduler admission policy (deadline orders the "
                         "queue by slack, FIFO among ties; the synthetic "
                         "requests get staggered deadlines so the order "
                         "actually differs from FIFO)")
    ap.add_argument("--replicas", default=None, metavar="B0,B1,...",
                    help="scale-out serving: comma-separated per-replica "
                         "decode batch sizes (e.g. '4,2,2' = one big + two "
                         "whimpy). Requests route through the Router "
                         "(repro.serve.router) instead of one Scheduler; "
                         "needs --requests, threads backend only")
    ap.add_argument("--topology", default=None, metavar="SPEC",
                    help="price the Router's dispatch with this cluster "
                         "topology's alpha-beta link costs (dist.topology "
                         "spec, e.g. 'hetero', '3node:eth1'); default: all "
                         "replicas equidistant")
    ap.add_argument("--route", choices=("least_loaded", "deadline"),
                    default="least_loaded",
                    help="Router dispatch policy: least_loaded books by "
                         "queue depth + page pressure + link cost; "
                         "deadline dispatches in slack order (and runs "
                         "each replica's scheduler in deadline mode)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace-event JSON (Perfetto-"
                         "loadable) of the run, with the metrics snapshot "
                         "embedded; inspect with python -m repro.obs.summary")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="inject the seeded random fault scenario "
                         "FaultPlan.sample_serve(SEED) — decode-slot faults "
                         "the scheduler recovers from by quarantine + "
                         "requeue; needs --requests")
    return ap


def main(argv=None):
    a = build_parser().parse_args(argv)

    if a.backend == "spmd" and a.devices and argv is None \
            and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={a.devices}"
        os.execv(sys.executable, [sys.executable] + sys.argv)

    import jax
    import numpy as np

    from repro.api import Engine, PartitionSpec, Plan, RunSpec, ServeSpec
    from repro.api.serving import Request, Scheduler
    from repro.configs import ARCHS, reduced as make_reduced
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = ARCHS[a.arch]
    if a.reduced:
        cfg = make_reduced(cfg)

    if not a.requests and (a.page_size or a.max_pages
                           or a.policy != "fifo" or a.chaos is not None
                           or a.share_prefix or a.evict or a.preempt):
        raise SystemExit(
            "--page-size/--max-pages/--policy/--chaos/--share-prefix/"
            "--evict/--preempt drive the continuous-batching scheduler; "
            "the aligned generate() path keeps the contiguous reference "
            "cache and would silently drop them — add --requests N")

    replica_batches = []
    if a.replicas:
        if not a.requests:
            raise SystemExit("--replicas routes requests over a replica "
                             "fleet; add --requests N")
        if a.backend != "threads":
            raise SystemExit("--replicas is threads-backend only (the "
                             "spmd mesh serves as a single replica)")
        replica_batches = [int(x) for x in a.replicas.split(",")]

    partition = PartitionSpec()
    if a.backend == "spmd":
        dsz, ssz, tsz = (int(x) for x in a.mesh.split(","))
        partition = PartitionSpec(data=dsz, stages=ssz, tp=tsz)
    elif replica_batches:
        partition = PartitionSpec(data=len(replica_batches))
    fault_kwargs = {}
    if a.chaos is not None:
        from repro.api import FaultPlan
        if replica_batches:
            faults = FaultPlan.sample_cluster(a.chaos,
                                              replicas=len(replica_batches))
        else:
            faults = FaultPlan.sample_serve(a.chaos, max_batch=a.batch)
        fault_kwargs = dict(faults=faults)
        print(f"chaos: {faults.describe()}")
    cluster_kwargs = {}
    if a.topology:
        from repro.api import ClusterSpec
        cluster_kwargs = dict(cluster=ClusterSpec(topology=a.topology))
    replica_kwargs = {}
    if replica_batches:
        from repro.api import ReplicaSpec
        replica_kwargs = dict(replicas=tuple(
            ReplicaSpec(max_batch=b) for b in replica_batches))
    plan = Plan(arch=cfg, partition=partition,
                serve=ServeSpec(prompt_len=a.prompt_len, gen=a.gen,
                                max_batch=max(replica_batches + [a.batch]),
                                temperature=a.temperature,
                                page_size=a.page_size,
                                max_pages=a.max_pages,
                                share_prefix=a.share_prefix,
                                evict=a.evict, preempt=a.preempt,
                                kernel_backend=a.kernel_backend,
                                **replica_kwargs),
                run=RunSpec(backend=a.backend),
                **cluster_kwargs, **fault_kwargs)
    from repro.obs import NULL_TRACER, Tracer
    tracer = Tracer() if a.trace else NULL_TRACER

    if replica_batches:
        from repro.api.serving import Request
        from repro.serve.router import Router
        rng = np.random.default_rng(1)

        def deadline(i):
            if a.route != "deadline":
                return 0
            return int(a.gen * (1 + (a.requests - i)))
        if a.share_prefix:
            pool = [rng.integers(0, cfg.vocab_size, a.prompt_len,
                                 dtype=np.int32)
                    for _ in range(max(1, a.requests // 4))]
            prompt_of = lambda i: pool[i % len(pool)].copy()
        else:
            prompt_of = lambda i: rng.integers(0, cfg.vocab_size,
                                               a.prompt_len, dtype=np.int32)
        reqs = [Request(rid=i, prompt=prompt_of(i), deadline=deadline(i))
                for i in range(a.requests)]
        router = Router(plan, policy=a.route, tracer=tracer)
        rep = router.run(reqs)
        if a.trace:
            print(f"trace: {tracer.export(a.trace)}")
        occ = rep.occupancy()
        print(f"arch={cfg.name} replicas={a.replicas} route={a.route} "
              f"topology={a.topology or 'flat'} requests={a.requests} "
              f"tokens={rep.tokens_out} "
              f"throughput={rep.tokens_per_s():.1f} tok/s "
              f"occupancy={'n/a' if occ is None else f'{occ:.2f}'}")
        print(f"router: dispatches={rep.router['dispatches']} "
              f"affinity_hits={rep.router['affinity_hits']} "
              f"rebalances={rep.router['rebalances']} "
              f"rounds={rep.router['rounds']} "
              f"replica_downs={rep.router['replica_downs']} "
              f"queue_peak={rep.router['queue_depth_peak']}")
        if a.share_prefix:
            print(f"memory: prefix_hit={rep.prefix_hit_tokens} tok "
                  f"shared={rep.pages_shared} evictions={rep.evictions}")
        lat = sorted(r.latency_s for r in rep.requests)
        print(f"latency: p50={lat[len(lat) // 2] * 1e3:.1f}ms "
              f"max={lat[-1] * 1e3:.1f}ms failed={rep.failed_requests}")
        return

    eng = Engine(plan, tracer=tracer)

    if a.requests:
        rng = np.random.default_rng(1)
        # deadline policy: staggered synthetic deadlines (in decode
        # steps), tighter for later arrivals, so slack ordering visibly
        # reorders the FIFO queue
        def deadline(i):
            if a.policy != "deadline":
                return 0
            return int(a.gen * (1 + (a.requests - i)))
        if a.share_prefix:
            # draw from a small prompt pool so prefixes actually repeat
            # and the index has something to hit
            pool = [rng.integers(0, cfg.vocab_size, a.prompt_len,
                                 dtype=np.int32)
                    for _ in range(max(1, a.requests // 4))]
            prompt_of = lambda i: pool[i % len(pool)].copy()
        else:
            prompt_of = lambda i: rng.integers(0, cfg.vocab_size,
                                               a.prompt_len, dtype=np.int32)
        reqs = [Request(rid=i, prompt=prompt_of(i), deadline=deadline(i))
                for i in range(a.requests)]
        rep = Scheduler(eng, policy=a.policy).run(reqs)
        if a.trace:
            print(f"trace: {tracer.export(a.trace)}")
        if a.chaos is not None:
            retries = sum(r.retries for r in rep.requests)
            print(f"faults: slot_faults={rep.slot_faults} "
                  f"requeues={rep.requeues} reprefills={rep.reprefills} "
                  f"quarantined={rep.quarantined} retries={retries} "
                  f"shed={rep.shed} failed={rep.failed_requests}")
        occ = rep.occupancy()       # None when no decode step ran (gen=1)
        pu = rep.page_utilization()
        print(f"arch={cfg.name} backend={a.backend} requests={a.requests} "
              f"slots={a.batch} tokens={rep.tokens_out} "
              f"decode={rep.ms_per_token():.1f}ms/tok "
              f"throughput={rep.tokens_per_s():.1f} tok/s "
              f"occupancy={'n/a' if occ is None else f'{occ:.2f}'} "
              f"pages={rep.peak_pages}/{rep.pages_total}"
              f"(x{rep.page_size} tok)"
              f" util={'n/a' if pu is None else f'{pu:.2f}'}")
        if a.share_prefix or a.evict or a.preempt:
            print(f"memory: prefix_hit={rep.prefix_hit_tokens} tok "
                  f"shared={rep.pages_shared} cow={rep.cow_copies} "
                  f"evictions={rep.evictions} "
                  f"readmits={rep.readmit_recomputes} "
                  f"preemptions={rep.preemptions}")
        lat = sorted(r.latency_s for r in rep.requests)
        print(f"latency: p50={lat[len(lat) // 2] * 1e3:.1f}ms "
              f"max={lat[-1] * 1e3:.1f}ms "
              f"ttft={rep.mean_ttft() * 1e3:.1f}ms "
              f"({rep.prefill_calls} prefill groups)")
        print("generated ids[rid=0]:", rep.requests[0].tokens)
        return

    rep = eng.generate()
    if a.trace:
        print(f"trace: {tracer.export(a.trace)}")
    print(f"arch={cfg.name} backend={a.backend} batch={a.batch} "
          f"prefill({a.prompt_len} tok)={rep.prefill_s * 1e3:.1f}ms "
          f"decode {rep.decode_steps} steps={rep.decode_s * 1e3:.1f}ms "
          f"({rep.ms_per_token():.1f} ms/tok)")
    print("generated ids[0]:", np.asarray(rep.tokens)[0].tolist())


if __name__ == "__main__":
    main()
