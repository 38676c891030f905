"""Kernel-level roofline + parity bench -> BENCH_kernels.json (committed).

Per kernel x shape cell:
  - analytic FLOPs / HBM bytes from the kernel's shape (formulas below),
    turned into roofline terms at the TPU v5e peaks benchmarks/roofline.py
    uses (197 TFLOP/s bf16, 819 GB/s HBM): t_compute, t_memory, the
    dominant term, and the compute/memory *fractions* of the bound time
    (compute_frac + memory_frac need not sum to 1 — each is its term over
    the max; the dominant one is 1.0).
  - measured wall-clock of the jnp reference path and the Pallas kernel in
    interpret mode on the host, plus their max abs error. Interpret mode
    executes the kernel body in Python, so the measured numbers are a
    *correctness* record, not a speed claim — the speed claim is the
    analytic roofline, which is what the CI schema check pins (fractions
    present for every kernel cell; missing cells fail rather than silently
    shrinking coverage).

Usage:
  PYTHONPATH=src python benchmarks/kernels_bench.py          # committed file
  PYTHONPATH=src python benchmarks/kernels_bench.py --tiny   # CI smoke
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from roofline import HBM_BW, PEAK_FLOPS          # noqa: E402

from repro.kernels import ref as kref            # noqa: E402
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.mamba_ssd import ssd_chunked
from repro.kernels.moe_gmm import grouped_matmul
from repro.kernels.rwkv6_scan import rwkv6_chunked

BYTES = 2                    # bf16 operand traffic on the deployment target

OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")

EXPECTED_KERNELS = ("flash_attention", "flash_decode", "flash_decode_paged",
                    "rwkv6_chunked", "ssd_chunked", "grouped_matmul")


def _roofline(flops: float, bytes_: float) -> dict:
    t_c = flops / PEAK_FLOPS
    t_m = bytes_ / HBM_BW
    bound = max(t_c, t_m)
    return dict(t_compute=t_c, t_memory=t_m,
                intensity=flops / bytes_,
                dominant="compute" if t_c >= t_m else "memory",
                compute_frac=t_c / bound, memory_frac=t_m / bound)


def _time(fn, *args, reps=3):
    out = jax.block_until_ready(fn(*args))           # warmup + compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3, out


def _err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _cell(kernel, shape, flops, bytes_, ref_fn, kern_fn):
    ref_ms, ref_out = _time(jax.jit(ref_fn))
    k_ms, k_out = _time(jax.jit(kern_fn))
    ref_leaf = ref_out[0] if isinstance(ref_out, tuple) else ref_out
    k_leaf = k_out[0] if isinstance(k_out, tuple) else k_out
    return dict(kernel=kernel, shape=shape, flops=flops, bytes=bytes_,
                roofline=_roofline(flops, bytes_),
                measured=dict(ref_ms=ref_ms, interpret_ms=k_ms,
                              max_abs_err=_err(ref_leaf, k_leaf)))


def bench_flash_attention(rng, tiny):
    cells = []
    # last shape crosses the roofline ridge (ai ~ (S+1)/4 > 240): the one
    # compute-bound cell in the committed file
    shapes = [(1, 4, 2, 64, 16, 0), (2, 8, 4, 256, 64, 0),
              (2, 8, 4, 256, 64, 128), (1, 8, 8, 1024, 64, 0)]
    if tiny:
        shapes = shapes[:1]
    for B, H, KV, S, hd, W in shapes:
        q = jnp.asarray(rng.standard_normal((B, H, S, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, KV, S, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, KV, S, hd)), jnp.float32)
        live = S * W - W * (W - 1) // 2 if W else S * (S + 1) // 2
        flops = 4.0 * B * H * hd * live                   # qk + pv, masked
        bytes_ = BYTES * (2 * B * H * S * hd + 2 * B * KV * S * hd)
        cells.append(_cell(
            "flash_attention",
            dict(B=B, H=H, KV=KV, S=S, hd=hd, window=W),
            flops, bytes_,
            lambda q=q, k=k, v=v, W=W: kref.attention_ref(
                q, k, v, causal=True, window=W),
            lambda q=q, k=k, v=v, W=W: flash_attention_fwd(
                q, k, v, causal=True, window=W, block_q=64, block_k=64,
                interpret=True)))
    return cells


def bench_flash_decode(rng, tiny):
    cells = []
    shapes = [(2, 4, 2, 128, 16), (4, 8, 4, 1024, 64)]
    if tiny:
        shapes = shapes[:1]
    for B, H, KV, S, hd in shapes:
        q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, KV, S, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, KV, S, hd)), jnp.float32)
        lens = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)
        mean_live = float(jnp.mean(lens))
        flops = 4.0 * B * H * hd * mean_live
        bytes_ = BYTES * (2 * B * KV * mean_live * hd + 2 * B * H * hd)
        cells.append(_cell(
            "flash_decode", dict(B=B, H=H, KV=KV, S=S, hd=hd),
            flops, bytes_,
            lambda q=q, k=k, v=v, lens=lens: kref.decode_ref(q, k, v, lens),
            lambda q=q, k=k, v=v, lens=lens: flash_decode(
                q, k, v, lens, block_k=128, interpret=True)))
    return cells


def bench_flash_decode_paged(rng, tiny):
    cells = []
    # groups, pages(+1 trash), page_size, B, KV, G, hd
    shapes = [(2, 8, 4, 2, 2, 2, 16), (2, 64, 16, 4, 4, 2, 64)]
    if tiny:
        shapes = shapes[:1]
    for L, P, ps, B, KV, G, hd in shapes:
        H = KV * G
        npg = P // B
        pool_k = jnp.asarray(
            rng.standard_normal((L, P + 1, KV, ps, hd)), jnp.float32)
        pool_v = jnp.asarray(
            rng.standard_normal((L, P + 1, KV, ps, hd)), jnp.float32)
        q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
        tab = jnp.asarray(
            rng.permutation(P)[:B * npg].reshape(B, npg), jnp.int32)
        lens = jnp.asarray(rng.integers(1, npg * ps + 1, B), jnp.int32)
        mean_live = float(jnp.mean(lens))
        flops = 4.0 * B * H * hd * mean_live
        # the fused walk reads only live pages; the gather baseline would
        # read (and write!) the full [B, npg*ps] view
        bytes_ = BYTES * (2 * B * KV * mean_live * hd + 2 * B * H * hd) \
            + 4 * B * npg
        cells.append(_cell(
            "flash_decode_paged",
            dict(groups=L, pages=P, page_size=ps, B=B, KV=KV, G=G, hd=hd),
            flops, bytes_,
            lambda q=q, pk=pool_k, pv=pool_v, t=tab, l=lens:
                kref.decode_paged_ref(q, pk, pv, t, l, layer=1),
            lambda q=q, pk=pool_k, pv=pool_v, t=tab, l=lens:
                flash_decode_paged(q, pk, pv, t, l, layer=1,
                                   interpret=True)))
    return cells


def bench_rwkv6(rng, tiny):
    cells = []
    shapes = [(1, 2, 32, 16, 16), (2, 4, 256, 64, 16)]
    if tiny:
        shapes = shapes[:1]
    for B, H, S, hd, C in shapes:
        r, k, v = (0.5 * jnp.asarray(rng.standard_normal((B, H, S, hd)),
                                     jnp.float32) for _ in range(3))
        # clip the log-decay like tests/test_kernels.py: per-step decay
        # below exp(-exp(1.386)) underflows the chunked cumulative products
        w = -jnp.exp(jnp.clip(
            jnp.asarray(rng.standard_normal((B, H, S, hd)), jnp.float32),
            -8.0, 1.386))
        u = jnp.asarray(rng.standard_normal((H, hd)), jnp.float32)
        # intra-chunk scores/y + inter-chunk state read + state update
        flops = 4.0 * B * H * S * C * hd + 6.0 * B * H * S * hd * hd
        bytes_ = 4 * (5 * B * H * S * hd + 2 * B * H * (S // C) * hd * hd)
        cells.append(_cell(
            "rwkv6_chunked", dict(B=B, H=H, S=S, hd=hd, chunk=C),
            flops, bytes_,
            lambda r=r, k=k, v=v, w=w, u=u: kref.rwkv6_ref(r, k, v, w, u),
            lambda r=r, k=k, v=v, w=w, u=u, C=C: rwkv6_chunked(
                r, k, v, w, u, chunk=C, interpret=True)))
    return cells


def bench_ssd(rng, tiny):
    cells = []
    shapes = [(1, 2, 64, 16, 16, 32), (2, 4, 256, 64, 64, 64)]
    if tiny:
        shapes = shapes[:1]
    for B, H, S, P, N, C in shapes:
        x = jnp.asarray(rng.standard_normal((B, H, S, P)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, H, S)), jnp.float32)
        B_ = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
        C_ = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
        a = -jnp.exp(jnp.asarray(rng.standard_normal(H), jnp.float32))
        flops = 2.0 * B * H * S * C * (N + P) + 4.0 * B * H * S * N * P
        bytes_ = 4 * (2 * B * H * S * P + 2 * B * S * N + B * H * S
                      + 2 * B * H * (S // C) * N * P)
        cells.append(_cell(
            "ssd_chunked", dict(B=B, H=H, S=S, P=P, N=N, chunk=C),
            flops, bytes_,
            lambda x=x, dt=dt, B_=B_, C_=C_, a=a: kref.ssd_ref(
                x, dt, B_, C_, a),
            lambda x=x, dt=dt, B_=B_, C_=C_, a=a, C=C: ssd_chunked(
                x, dt, B_, C_, a, chunk=C, interpret=True)))
    return cells


def bench_gmm(rng, tiny):
    cells = []
    shapes = [(4, 32, 32, 64), (8, 128, 128, 256)]
    if tiny:
        shapes = shapes[:1]
    for E, Cp, d, f in shapes:
        x = jnp.asarray(rng.standard_normal((E, Cp, d)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((E, d, f)), jnp.float32)
        flops = 2.0 * E * Cp * d * f
        bytes_ = BYTES * (E * Cp * d + E * d * f + E * Cp * f)
        cells.append(_cell(
            "grouped_matmul", dict(E=E, C=Cp, d=d, f=f),
            flops, bytes_,
            lambda x=x, w=w: kref.gmm_ref(x, w),
            lambda x=x, w=w: grouped_matmul(x, w, interpret=True)))
    return cells


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="one small shape per kernel (CI smoke)")
    ap.add_argument("--out", default=OUT)
    a = ap.parse_args(argv)
    rng = np.random.default_rng(0)
    cells = []
    for bench in (bench_flash_attention, bench_flash_decode,
                  bench_flash_decode_paged, bench_rwkv6, bench_ssd,
                  bench_gmm):
        cells.extend(bench(rng, a.tiny))
    doc = dict(meta=dict(mode="tiny" if a.tiny else "full",
                         peak_flops=PEAK_FLOPS, hbm_bw=HBM_BW,
                         dtype_bytes=BYTES,
                         kernels=list(EXPECTED_KERNELS)),
               kernels=cells)
    missing = set(EXPECTED_KERNELS) - {c["kernel"] for c in cells}
    assert not missing, f"bench produced no cells for {sorted(missing)}"
    with open(a.out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    for c in cells:
        r = c["roofline"]
        m = c["measured"]
        print(f"{c['kernel']:20s} {str(c['shape']):58s} "
              f"dom={r['dominant']:7s} cf={r['compute_frac']:.2f} "
              f"mf={r['memory_frac']:.2f} ai={r['intensity']:7.1f} "
              f"ref={m['ref_ms']:7.1f}ms interp={m['interpret_ms']:8.1f}ms "
              f"err={m['max_abs_err']:.2e}")
    print(f"wrote {os.path.normpath(a.out)} ({len(cells)} cells)")


if __name__ == "__main__":
    main()
