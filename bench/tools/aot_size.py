"""Compile a cell's steps at their real shapes for a described TPU v5e and
print what memory_analysis() says they need, to size the cell (the serve
cells' page pool) before any chip run. Nothing runs; no chip is needed.

    JAX_PLATFORMS=cpu python bench/tools/aot_size.py <workload> \
        [--max-pages N ...] [--max-batch B] [--seq S]

Serve cells: the Scheduler's paged prefill and decode steps at the cell's
max_batch, prompt_len and gen, for each --max-pages given (0 = the cell
file's). Train cells: one virtual worker's local wave step.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import common  # noqa: E402


def analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k + "_size_in_bytes")) for k in
           ("argument", "output", "temp", "alias", "generated_code")}
    out["total"] = (out["argument"] + out["output"] + out["temp"]
                    - out["alias"])
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--max-pages", type=int, nargs="*", default=[0])
    ap.add_argument("--max-batch", type=int, default=0,
                    help="try another slot count than the cell file's")
    ap.add_argument("--seq", type=int, default=0,
                    help="try another sequence length than the traffic's")
    a = ap.parse_args()
    w, cfg, traffic, cell = common.cell_files(a.workload,
                                              common.benchmark_spec())
    if a.max_batch:
        cell = dict(cell, max_batch=a.max_batch)
    if a.seq:
        traffic = dict(traffic, seq=a.seq)
    common.program()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.api import Engine
    from repro.models import lm

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    arch = common.arch_for(cfg)
    params = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                          lm.param_shapes(arch))
    if traffic["driver"] == "train_wsp":
        from bench.drivers import train_wsp
        from repro.core import wave
        from repro.optim import make_optimizer
        plan = train_wsp.plan_for(arch, traffic, 0)
        locked = wave.build_local_wave_step(
            arch, plan.num_microbatches,
            make_optimizer(traffic["optimizer"], traffic["lr"]))
        step = next(c.cell_contents for c in locked.__closure__
                    if hasattr(c.cell_contents, "lower"))
        B, S = traffic["batch"], traffic["seq"]
        c = step.lower(params, {"step": sds((), jnp.int32)},
                       sds((B, S), jnp.int32),
                       sds((B, S), jnp.int32)).compile()
        print(a.workload, "wave_step", analysis(c), flush=True)
        return
    from bench.drivers import serve_backlog
    from repro.serve import cache as cache_lib
    for mp in a.max_pages:
        c2 = dict(cell, max_pages=mp or cell.get("max_pages", 0))
        plan = serve_backlog.plan_for(arch, traffic, c2, cfg["kernel_backend"])
        sv = plan.serve
        eng = Engine(plan, params=params)
        pre, dec, p = eng.serve_steps()
        layout = cache_lib.make_layout(sv.max_batch, sv.max_len,
                                       page_size=sv.page_size,
                                       max_pages=sv.max_pages)
        _, cdt = lm.serve_dtypes(plan.run.compute_dtype, sv.cache_dtype)
        tree, _ = cache_lib.paged_struct(arch, layout, dtype=cdt)
        tree = jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)
        B, P = sv.max_batch, sv.prompt_len
        pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
        cp = pre.lower(p, sds((B, P), jnp.int32), sds((B,), jnp.int32),
                       tree).compile()
        print(a.workload, f"pages={layout.num_pages} cache_bytes={pool}",
              "prefill", analysis(cp), "kernels",
              cp.as_text().count("tpu_custom_call"), flush=True)
        cd = dec.lower(p, sds((B, 1), jnp.int32), tree,
                       sds((B,), jnp.int32)).compile()
        print(a.workload, f"pages={layout.num_pages}", "decode",
              analysis(cd), "kernels", cd.as_text().count("tpu_custom_call"),
              flush=True)


if __name__ == "__main__":
    main()
