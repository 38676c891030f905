"""Bring-up smoke test: the main paths on a TPU at qwen3-0.6b's published
widths (28 layers, d_model 1024, 16 heads / 8 KV heads of 128, d_ff 3072,
vocab 151,936), random weights from --seed.

    python chip_smoke.py               # one chip: serving leg + training leg
    python chip_smoke.py --four-chip   # four chips: spmd wave step only

Serving leg: 16 requests (prompt lengths drawn from --seed over 64..512)
through Engine + Scheduler on the paged pool with the compiled Pallas
kernels (kernel_backend="tpu"), the path `launch/serve.py --requests` takes.
It prints compile times, the Mosaic kernel count of each compiled step, the
tokens served, peak device memory, and the largest logit difference against
the same requests on the jnp path (kernel_backend="ref").

Training leg: Engine.fit() on a WSP(D=1) Plan with two virtual workers on
the one chip (the threaded runtime), a few waves at batch 16 x 256.

--four-chip runs only the spmd pipelined wave step on a (data, stage, tp) =
(1, 2, 2) mesh against the same Plan and seed on (1, 1, 1), in one process,
and checks that the losses agree and the parameters are spread over all
four devices.

Run it from the root of a checkout, one process per chip (it starts no
child). It exits non-zero, printing no result, where JAX finds no TPU or
the checkout's src/ is missing. Its last stdout line is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
The numbers it prints are bring-up checks, not benchmark figures.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH_NAME = "qwen3-0.6b"
# Logit parity vs the jnp path: both run the same f32 model, and the TPU
# multiplies f32 matrices outside the kernels in one bf16 pass (relative
# error ~2^-9 per product); the two attention implementations round
# differently and the difference compounds through 28 residual layers. So
# the bound is relative to the logit range: 5% of max|ref logit|.
LOGIT_RTOL = 0.05
# Loss agreement of the (1,2,2) mesh vs (1,1,1): tensor-parallel partial
# sums reduce in a different order (f32 at the TPU's default matmul
# precision): 1e-3 of the loss (about 12 nats at random init). Two waves:
# wave 0 is the forward pass at identical weights, wave 1 follows one
# gradient all-reduce and update. SGD at the Plan's lr 0.3 amplifies the
# rounding difference about tenfold per wave (measured on a v5e: 7e-6,
# 2e-4, 2e-3 of the loss over waves 0-2), so later waves test chaos, not
# the sharding.
LOSS_RTOL = 1e-3


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(msg, flush=True)


def kernel_calls(compiled) -> int:
    """Mosaic (Pallas) kernels in a compiled program's HLO."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def memory(device):
    stats = device.memory_stats() or {}
    return (f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_in_use={stats.get('bytes_in_use')}")


# ---------------------------------------------------------------------------
# serving leg
# ---------------------------------------------------------------------------
def serve_leg(arch, *, seed, kernel_backend="tpu", requests=16, max_batch=8,
              prompt_len=512, gen=32, page_size=128, min_prompt=64):
    import jax
    import numpy as np

    from repro.api import Engine, Plan, RunSpec, ServeSpec
    from repro.api.serving import Request, Scheduler
    from repro.models import lm

    params, _ = lm.init_params(arch, jax.random.PRNGKey(seed))

    def engine(kb):
        spec = ServeSpec(prompt_len=prompt_len, gen=gen, max_batch=max_batch,
                         page_size=page_size, kernel_backend=kb)
        return Engine(Plan(arch=arch, serve=spec, run=RunSpec(seed=seed)),
                      params=params)

    rng = np.random.default_rng(seed)
    lens = rng.integers(min_prompt, prompt_len + 1, requests)
    prompts = [rng.integers(0, arch.vocab_size, n, dtype=np.int32)
               for n in lens]
    B = max_batch
    group = list(range(B))                 # the first admission group
    pad = np.zeros((B, prompt_len), np.int32)
    for j in group:
        pad[j, :lens[j]] = prompts[j]
    lens_b = np.asarray(lens[:B], np.int32)

    def fill(store):
        for j in group:
            store.alloc(j, int(lens[j]) + gen)
        return store

    # ---- compile the kernel path's steps ahead of time --------------------
    eng = engine(kernel_backend)
    pre, dec, p = eng.serve_steps()
    store = fill(eng.serve_store())
    t0 = time.monotonic()
    c_pre = pre.lower(p, pad, lens_b, store.prefill_input(group)).compile()
    t_pre = time.monotonic() - t0
    t0 = time.monotonic()
    c_dec = dec.lower(p, np.zeros((B, 1), np.int32), store.tree,
                      lens_b).compile()
    t_dec = time.monotonic() - t0
    n_pre, n_dec = kernel_calls(c_pre), kernel_calls(c_dec)
    log(f"serve: compile prefill={t_pre:.2f}s decode={t_dec:.2f}s")
    log(f"serve: tpu_custom_call prefill={n_pre} decode={n_dec}")
    if kernel_backend == "tpu":
        check(n_pre >= 1 and n_dec >= 1,
              "the compiled serve steps contain no Mosaic kernel")
    del c_pre, c_dec

    # ---- logit parity vs the jnp path on the first admission group --------
    ref = engine("ref")
    out = {}
    for name, e, s in (("ref", ref, fill(ref.serve_store())),
                       (kernel_backend, eng, store)):
        pf = np.asarray(e.prefill_into(s, pad, lens_b, group))
        if name == "ref":
            first = np.argmax(pf, axis=-1).astype(np.int32)[:, None]
        dl, _ = e.decode(first, s, lens_b)
        out[name] = (pf, np.asarray(dl))
        del s
    del store
    gc.collect()
    for i, what in enumerate(("prefill", "decode")):
        a, b = out["ref"][i], out[kernel_backend][i]
        check(a.shape == b.shape == (B, arch.vocab_size),
              f"{what} logits shape {b.shape}")
        check(np.isfinite(b).all(), f"{what} logits are not finite")
        diff = float(np.max(np.abs(a - b)))
        scale = float(np.max(np.abs(a)))
        log(f"serve: {what} logits max|{kernel_backend}-ref|={diff:.6g} "
            f"max|ref|={scale:.6g} tol={LOGIT_RTOL * scale:.6g}")
        check(diff <= LOGIT_RTOL * scale,
              f"{what} logits differ from the ref path by {diff}")
    del ref, out

    # ---- 16 requests through the Scheduler --------------------------------
    reqs = [Request(rid=i, prompt=prompts[i]) for i in range(requests)]
    t0 = time.monotonic()
    rep = Scheduler(eng).run(reqs)
    wall = time.monotonic() - t0
    got = {r.rid: r for r in rep.requests}
    check(len(got) == requests, f"{len(got)}/{requests} requests retired")
    bad = [r.rid for r in rep.requests
           if r.failed or r.shed or len(r.tokens) != gen]
    check(not bad and rep.failed_requests == 0,
          f"requests failed or short of {gen} tokens: {bad}")
    log(f"serve: requests={requests} tokens_out={rep.tokens_out} "
        f"prefill_groups={rep.prefill_calls} decode_steps={rep.decode_steps} "
        f"pages_peak={rep.peak_pages}/{rep.pages_total} wall={wall:.2f}s")
    del eng, p, pre, dec, params, rep
    gc.collect()


# ---------------------------------------------------------------------------
# training leg: the threaded WSP runtime
# ---------------------------------------------------------------------------
def train_leg(arch, *, seed, waves=3, num_vw=2, batch=16, seq=256):
    from repro.api import ClusterSpec, Engine, Plan, RunSpec, WSP

    plan = Plan(arch=arch, cluster=ClusterSpec(num_vw=num_vw),
                sync=WSP(D=1),
                run=RunSpec(max_waves=waves, batch=batch, seq=seq,
                            seed=seed, data_seed=seed))
    eng = Engine(plan)
    rep = eng.fit()
    losses = [l for _, _, l in rep.losses]
    log(f"train: waves={rep.waves}/{rep.waves_requested} "
        f"wall={rep.wall_s:.2f}s crashes={rep.crashes}")
    for wid, w in sorted(eng.workers.items()):
        log(f"train: {wid} losses={[round(l, 4) for l in w.metrics.losses]} "
            f"wave_s={[round(t, 2) for t in w.metrics.wave_times]}")
    check(all(w.done for w in eng.workers.values()),
          "a virtual worker did not finish its waves")
    check(rep.waves == rep.waves_requested == waves * num_vw,
          f"{rep.waves} of {waves * num_vw} waves ran")
    check(losses and all(math.isfinite(l) for l in losses),
          f"non-finite losses: {losses}")
    del eng, rep
    gc.collect()


# ---------------------------------------------------------------------------
# four chips: the spmd pipelined wave step, (1,2,2) vs (1,1,1)
# ---------------------------------------------------------------------------
def four_chip_leg(arch, *, seed, waves=2, batch=16, seq=256):
    import jax

    from repro.api import Engine, PartitionSpec, Plan, RunSpec, WSP

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chip needs 4 devices, "
                             f"JAX sees {len(devices)}")

    def fit(stages, tp):
        plan = Plan(arch=arch, partition=PartitionSpec(stages=stages, tp=tp),
                    sync=WSP(D=0),
                    run=RunSpec(backend="spmd", max_waves=waves, batch=batch,
                                seq=seq, seed=seed, data_seed=seed))
        eng = Engine(plan)
        t0 = time.monotonic()
        rep = eng.fit()
        losses = [l for _, _, l in rep.losses]
        log(f"four-chip: mesh=(1,{stages},{tp}) losses="
            f"{[round(l, 6) for l in losses]} wall={time.monotonic() - t0:.2f}s")
        check(len(losses) == waves and all(math.isfinite(l) for l in losses),
              f"mesh (1,{stages},{tp}): losses {losses}")
        return eng, losses

    eng, mesh_losses = fit(2, 2)
    # the live device tree of the (1,2,2) engine: where each shard sits
    params = eng._spmd["params"]
    held = {d.id: 0 for d in devices[:4]}
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    for d in devices[:4]:
        log(f"four-chip: device {d.id} holds {held[d.id]} of {total} "
            f"parameter bytes; memory_stats={d.memory_stats()}")
    check(set(held) == {d.id for d in devices[:4]} and all(held.values()),
          f"parameter shards do not cover all four devices: {held}")
    check(max(held.values()) < total,
          "a device holds the whole model: the parameters are not sharded")
    del eng, params
    gc.collect()

    eng, one_losses = fit(1, 1)
    del eng
    for w, (a, b) in enumerate(zip(one_losses, mesh_losses)):
        log(f"four-chip: wave {w} loss (1,1,1)={a:.6f} (1,2,2)={b:.6f} "
            f"|diff|={abs(a - b):.3g} tol={LOSS_RTOL * abs(a):.3g}")
        check(abs(a - b) <= LOSS_RTOL * abs(a),
              f"wave {w}: (1,2,2) loss {b} vs (1,1,1) {a}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the (1,2,2)-vs-(1,1,1) spmd wave step "
                         "comparison on four chips")
    a = ap.parse_args(argv)
    try:
        from repro.configs import ARCHS
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: run this from a checkout of the repo ({e})",
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {platform}",
              file=sys.stderr)
        return 2
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{enable_compile_cache()}")
    arch = ARCHS[ARCH_NAME]
    try:
        if a.four_chip:
            four_chip_leg(arch, seed=a.seed)
        else:
            serve_leg(arch, seed=a.seed)
            log(f"serve: {memory(devices[0])}")
            train_leg(arch, seed=a.seed)
            log(f"train: {memory(devices[0])}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
