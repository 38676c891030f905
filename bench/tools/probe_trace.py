"""Record a small profiler trace of the program's serve steps on the chip
and print how it is laid out (planes, lines, event names), so that
bench/trace_reduce.py can match them by name.

    python bench/tools/probe_trace.py [--out bench-out/probe]

Two small traces, small enough to keep as the trace reduction's test
fixtures: a qwen3-shaped model at a tiny width (two layers, heads of 128)
serving one prefill group and three decode steps on the paged pool with
the Pallas kernels, inside the harness's annotation names
(serve_tiny.xplane.pb); and three waves of each of two virtual workers of
the threaded WSP runtime at a tiny size (wsp_tiny.xplane.pb, kept under
bench/tests/data/).
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import common  # noqa: E402


def show(path, limit=40):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r} events={len(evs)}")
            seen = []
            for e in evs:
                if e.name not in seen:
                    seen.append(e.name)
            for n in seen[:limit]:
                e = next(x for x in evs if x.name == n)
                stats = {}
                try:
                    stats = dict(e.stats)
                except Exception as ex:       # noqa: BLE001 - printing only
                    stats = {"err": repr(ex)}
                print(f"    {n!r} start_ns={e.start_ns} dur_ns={e.duration_ns}"
                      f" stats={ {k: str(v)[:60] for k, v in stats.items()} }")


def options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0          # Python call events swamp the trace
    o.host_tracer_level = 2
    return o


def newest_xplane(d):
    got = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                           recursive=True), key=os.path.getmtime)
    return got[-1]


def tiny_serve(out):
    """One prefill group and three decode steps of a two-layer qwen3-shaped
    model on the Pallas kernels, inside the harness's annotation names."""
    import jax
    import numpy as np
    from repro.api import Engine, Plan, ServeSpec
    from repro.configs import ARCHS, reduced
    from repro.models import lm

    arch = reduced(ARCHS["qwen3-0.6b"], num_layers=2, d_model=256, d_ff=512,
                   vocab_size=1024, num_heads=2, num_kv_heads=1, head_dim=128,
                   stages=1)
    params = common.make_params(lm.param_shapes(arch), arch.num_layers, 3)
    spec = ServeSpec(prompt_len=256, gen=32, max_batch=4, page_size=128,
                     kernel_backend="tpu")
    eng = Engine(Plan(arch=arch, serve=spec), params=params)
    store = eng.serve_store()
    prompts = np.random.default_rng(0).integers(
        0, arch.vocab_size, (4, 256)).astype(np.int32)
    lens = np.array([256, 200, 100, 60], np.int32)
    for s in range(4):
        store.alloc(s, int(lens[s]) + 32)
    tok = np.asarray(eng.prefill_into(store, prompts, lens, [0, 1, 2, 3]))
    tok = tok.argmax(-1).astype(np.int32)[:, None]
    jax.block_until_ready(eng.decode(tok, store, lens)[0])
    d = os.path.join(out, "tiny_raw")
    jax.profiler.start_trace(d, profiler_options=options())
    with jax.profiler.TraceAnnotation("engine.prefill_into"):
        np.asarray(eng.prefill_into(store, prompts, lens, [0, 1, 2, 3]))
    pos = lens.copy()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("engine.decode"):
            logits, _ = eng.decode(tok, store, pos)
            jax.block_until_ready(logits)
        with jax.profiler.TraceAnnotation("logits_to_host"):
            np.asarray(logits)
        pos = pos + 1
        time.sleep(0.005)
    jax.profiler.stop_trace()
    p = newest_xplane(d)
    dst = os.path.join(out, "serve_tiny.xplane.pb")
    shutil.copy(p, dst)
    print("tiny serve trace", dst, os.path.getsize(dst))
    return dst


def tiny_wsp(out):
    """Three WSP waves of each of two virtual workers, traced after a run
    of the same Plan has compiled the wave step."""
    import jax
    from repro.api import ClusterSpec, Engine, Plan, RunSpec, WSP
    from repro.configs import ARCHS, reduced

    arch = reduced(ARCHS["qwen3-0.6b"], num_layers=2, d_model=128, d_ff=256,
                   vocab_size=512, num_heads=2, num_kv_heads=1, head_dim=64,
                   stages=1)
    plan = Plan(arch=arch, cluster=ClusterSpec(num_vw=2), sync=WSP(D=1),
                run=RunSpec(max_waves=3, batch=4, seq=128, seed=1,
                            data_seed=1))
    Engine(plan).fit()
    d = os.path.join(out, "wsp_raw")
    jax.profiler.start_trace(d, profiler_options=options())
    Engine(plan).fit()
    jax.profiler.stop_trace()
    dst = os.path.join(out, "wsp_tiny.xplane.pb")
    shutil.copy(newest_xplane(d), dst)
    print("tiny wsp trace", dst, os.path.getsize(dst))
    return dst


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="bench-out/probe")
    a = ap.parse_args()
    common.program()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    os.makedirs(a.out, exist_ok=True)
    show(tiny_serve(a.out), limit=8)
    show(tiny_wsp(a.out), limit=8)


if __name__ == "__main__":
    main()
