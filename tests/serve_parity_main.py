"""Subprocess body for serve-path parity on a real pipelined mesh (needs
4 fake devices, so it must own the process — XLA device count is locked at
first jax import).

Checks, per architecture family:
  1. build_prefill_step / build_decode_step logits match the forward_ref
     cache path to float tolerance on an aligned greedy rollout;
  2. Engine.generate() on the spmd backend produces bit-identical tokens
     to the threads (forward_ref) backend;
  3. the continuous-batching Scheduler produces identical per-request
     token streams on both backends (staggered per-row positions through
     the pipelined decode step);
  4. the same holds with a paged KV pool (page_size < prompt_len): block-
     table reads/writes through the pipeline scan reproduce the
     contiguous-degenerate streams bit for bit on both backends;
  5. with ServeSpec.share_prefix, repeated prompts served through
     refcounted shared pages (prefill skipping the matched prefix)
     reproduce the unshared paged streams bit for bit on both backends;
  6. with ServeSpec.kernel_backend="interpret" the Pallas kernels own the
     hot paths — paged decode walks the KV pool through the block table
     inside flash_decode_paged (per-row lengths, no gathered view) — and
     the token streams stay bit-identical to the jnp "ref" oracle, for
     plain paged, shared-prefix, and (full-attention) fp8 KV pools.

Run: python tests/serve_parity_main.py <arch> <seed>
"""
import os
import sys
from dataclasses import replace as dc_replace

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402
import numpy as np                           # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.api import (Engine, PartitionSpec, Plan, RunSpec,  # noqa: E402
                       ServeSpec)
from repro.api.serving import Request, Scheduler  # noqa: E402
from repro.configs import ARCHS, reduced, RunConfig, ShapeConfig  # noqa: E402
from repro.core import wave                  # noqa: E402
from repro.launch.mesh import make_mesh_auto  # noqa: E402
from repro.models import lm                  # noqa: E402

PROMPT, GEN, B = 8, 6, 4


def _cfg(arch_name: str):
    over = {}
    if ARCHS[arch_name].attn_type == "swa":
        over["window_size"] = 6          # < max_len: exercise ring wrap
    return reduced(ARCHS[arch_name], stages=2, tp=2, num_layers=4,
                   num_microbatches=2, **over)


def step_level_parity(cfg, params, pspecs, prompts) -> None:
    """build_prefill_step/build_decode_step vs the forward_ref oracle."""
    mesh = make_mesh_auto((1, 2, 2), ("data", "stage", "tp"))
    max_len = PROMPT + GEN
    common = dict(arch=cfg, compute_dtype="float32")
    rc_pre = RunConfig(shape=ShapeConfig("p", PROMPT, B, "prefill"),
                       **common)
    rc_dec = RunConfig(shape=ShapeConfig("d", max_len, B, "decode"),
                       **common)
    pre_step, _, _ = wave.build_prefill_step(rc_pre, mesh, cache_len=max_len)
    dec_step, _, _ = wave.build_decode_step(rc_dec, mesh, pos_per_row=True)
    with jax.set_mesh(mesh):
        p_sh = jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P)))
        cache = lm.init_cache(cfg, B, max_len, dtype=jnp.float32)
        logits, cache = jax.jit(pre_step)(p_sh, {"inputs": prompts,
                                                 "cache": cache})

    ref_cache = lm.init_cache(cfg, B, max_len, dtype=jnp.float32)
    hid, ref_cache, _ = lm.forward_ref(cfg, params, prompts, mode="prefill",
                                       cache=ref_cache)
    ref_logits = lm.logits_ref(cfg, params, hid[:, -1:])
    pd = float(jnp.max(jnp.abs(logits - ref_logits)))
    assert pd < 1e-3, f"prefill logits diff {pd}"
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    ref_tok = jnp.argmax(ref_logits[:, -1], axis=-1)
    assert np.array_equal(np.asarray(tok), np.asarray(ref_tok))

    dd = 0.0
    for t in range(1, GEN):
        pos = jnp.full((B,), PROMPT + t - 1, jnp.int32)
        with jax.set_mesh(mesh):
            logits, cache = jax.jit(dec_step)(
                p_sh, {"inputs": tok[:, None], "cache": cache, "pos": pos})
        hid, ref_cache, _ = lm.forward_ref(
            cfg, params, ref_tok[:, None], mode="decode", cache=ref_cache,
            pos=jnp.int32(PROMPT + t - 1))
        ref_lg = lm.logits_ref(cfg, params, hid)
        dd = max(dd, float(jnp.max(jnp.abs(logits - ref_lg))))
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        ref_tok = jnp.argmax(ref_lg[:, -1], axis=-1)
        assert np.array_equal(np.asarray(tok), np.asarray(ref_tok)), \
            f"greedy tokens diverged at step {t}"
    print(f"step_logits_diff={dd:.3e}")


def main(arch_name: str, seed: int) -> int:
    cfg = _cfg(arch_name)
    params, pspecs = lm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, PROMPT)),
                          jnp.int32)

    step_level_parity(cfg, params, pspecs, prompts)

    # Engine-level parity: same Plan, spmd mesh vs threads (forward_ref)
    serve = ServeSpec(prompt_len=PROMPT, gen=GEN, max_batch=B)
    spmd = Plan(arch=cfg, serve=serve,
                partition=PartitionSpec(stages=2, tp=2, data=1),
                run=RunSpec(backend="spmd"))
    ref = Plan(arch=cfg, serve=serve)
    rep_s = Engine(spmd).generate(prompts)
    rep_r = Engine(ref).generate(prompts)
    assert np.array_equal(rep_s.tokens, rep_r.tokens), \
        (rep_s.tokens, rep_r.tokens)
    print("generate_tokens_identical=1")

    # Scheduler parity: staggered admissions drive the per-row position
    # vector through the pipelined decode step
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, PROMPT,
                                        dtype=np.int32),
                    max_new_tokens=int(rng.integers(2, GEN + 1)))
            for i in range(2 * B)]
    out_s = Scheduler(Engine(spmd)).run(list(reqs))
    out_r = Scheduler(Engine(ref)).run(list(reqs))
    for a, b in zip(out_s.requests, out_r.requests):
        assert a.rid == b.rid and a.tokens == b.tokens, (a.rid, a.tokens,
                                                         b.tokens)
    assert out_s.tokens_out == sum(r.max_new_tokens for r in reqs)
    print("scheduler_tokens_identical=1")

    # Paged parity: page_size < prompt_len splits every slot's KV across
    # pages; streams must match the contiguous-degenerate runs above on
    # both backends
    paged = ServeSpec(prompt_len=PROMPT, gen=GEN, max_batch=B, page_size=4)
    out_ps = Scheduler(Engine(spmd.replace(serve=paged))).run(list(reqs))
    out_pr = Scheduler(Engine(ref.replace(serve=paged))).run(list(reqs))
    for a, b, c in zip(out_ps.requests, out_pr.requests, out_r.requests):
        assert a.rid == b.rid == c.rid
        assert a.tokens == b.tokens == c.tokens, (a.rid, a.tokens, b.tokens,
                                                  c.tokens)
    if cfg.attn_type == "full":
        assert out_ps.pages_total == out_pr.pages_total > B  # really paged
    else:
        # all-windowed / attention-free: no full-attention KV group, so
        # no page pool to ration (fixed-size per-slot state only)
        assert out_ps.pages_total == out_pr.pages_total == 0
    print("paged_scheduler_tokens_identical=1")

    # Shared-prefix paged parity: every even rid repeats rid 0's prompt,
    # so the prefix index maps them onto shared refcounted pages and
    # prefill skips the matched writes — streams must still match the
    # unshared paged run bit for bit on both backends
    s_reqs = [Request(rid=i,
                      prompt=(reqs[0] if i % 2 == 0 else reqs[i])
                      .prompt.copy(),
                      max_new_tokens=reqs[i].max_new_tokens)
              for i in range(2 * B)]
    shared = ServeSpec(prompt_len=PROMPT, gen=GEN, max_batch=B, page_size=4,
                       share_prefix=True)
    out_ss = Scheduler(Engine(spmd.replace(serve=shared))).run(
        [Request(r.rid, r.prompt.copy(), r.max_new_tokens) for r in s_reqs])
    out_sr = Scheduler(Engine(ref.replace(serve=shared))).run(
        [Request(r.rid, r.prompt.copy(), r.max_new_tokens) for r in s_reqs])
    out_ur = Scheduler(Engine(ref.replace(serve=paged))).run(s_reqs)
    for a, b, c in zip(out_ss.requests, out_sr.requests, out_ur.requests):
        assert a.rid == b.rid == c.rid
        assert a.tokens == b.tokens == c.tokens, (a.rid, a.tokens, b.tokens,
                                                  c.tokens)
    if cfg.attn_type == "full":
        # the page accounting is backend-independent too (peak contrasts
        # vs unshared live in benchmarks/serve_bench.py's squeezed pool)
        assert out_ss.prefix_hit_tokens > 0
        assert out_ss.prefix_hit_tokens == out_sr.prefix_hit_tokens
        assert out_ss.peak_pages == out_sr.peak_pages
        assert out_ss.pages_shared == out_sr.pages_shared > 0
    else:
        assert out_ss.prefix_hit_tokens == out_sr.prefix_hit_tokens == 0
    print("shared_prefix_tokens_identical=1")

    # Kernel-backend parity: the same staggered request mix through the
    # Pallas kernels in interpret mode (threads backend; decode consumes
    # the paged pool + block table directly inside flash_decode_paged with
    # per-row lengths). Streams must match the jnp "ref" runs bit for bit.
    interp = dc_replace(paged, kernel_backend="interpret")
    out_ki = Scheduler(Engine(ref.replace(serve=interp))).run(list(reqs))
    for a, b in zip(out_ki.requests, out_pr.requests):
        assert a.rid == b.rid and a.tokens == b.tokens, (a.rid, a.tokens,
                                                         b.tokens)
    out_ks = Scheduler(Engine(ref.replace(
        serve=dc_replace(shared, kernel_backend="interpret")))).run(
        [Request(r.rid, r.prompt.copy(), r.max_new_tokens) for r in s_reqs])
    for a, b in zip(out_ks.requests, out_sr.requests):
        assert a.rid == b.rid and a.tokens == b.tokens, (a.rid, a.tokens,
                                                         b.tokens)
    if cfg.attn_type == "full":
        # fp8 KV pages quantize both backends identically (the kernel
        # reads the pool pages as stored, casting in-register)
        f8 = dc_replace(paged, cache_dtype="f8")
        out_f8r = Scheduler(Engine(ref.replace(serve=f8))).run(list(reqs))
        out_f8i = Scheduler(Engine(ref.replace(
            serve=dc_replace(f8, kernel_backend="interpret")))).run(
            list(reqs))
        for a, b in zip(out_f8i.requests, out_f8r.requests):
            assert a.rid == b.rid and a.tokens == b.tokens, (a.rid,
                                                             a.tokens,
                                                             b.tokens)
    print("kernel_backend_tokens_identical=1")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 0))
