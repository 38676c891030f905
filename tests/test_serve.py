"""Serve-mode Plans through the Engine: Plan validation, generate() parity
with the forward_ref oracle, continuous-batching scheduler invariants, and
the subprocess parity harness on a real pipelined mesh (the three serve
arch families of examples/serve_batched.py: dense GQA, sliding-window,
RWKV6)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (BSP, ClusterSpec, Engine, PartitionSpec, Plan,
                       RunSpec, ServeSpec, WSP, get_preset)
from repro.api.serving import Request, Scheduler
from repro.configs import ARCHS, ShapeConfig, reduced
from repro.models import lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE_ARCHS = ("qwen3-0.6b", "h2o-danube-1.8b", "rwkv6-3b")

# seeded prompt/request cases, test_wsp.py-style
_R = np.random.default_rng(31)
_PARITY_CASES = [(a, int(_R.integers(0, 1_000))) for a in SERVE_ARCHS]
_SCHED_CASES = [(int(_R.integers(0, 1_000)), int(_R.integers(2, 4)),
                 int(_R.integers(3, 8))) for _ in range(4)]


def _cfg(name: str, **over):
    base = dict(num_layers=2, d_model=32, d_ff=64, vocab_size=256,
                num_microbatches=2)
    if ARCHS[name].attn_type == "swa":
        base["window_size"] = 6        # < max_len: exercise the ring wrap
    base.update(over)
    return reduced(ARCHS[name], **base)


def _prompts(cfg, seed, b, p):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (b, p)), jnp.int32)


# ---------------------------------------------------------------------------
# Plan validation: serve knobs on train Plans and vice versa
# ---------------------------------------------------------------------------
def test_serve_plan_validation():
    cfg = _cfg("qwen3-0.6b")
    sv = ServeSpec(prompt_len=8, gen=4, max_batch=2)
    with pytest.raises(ValueError, match="arch is required"):
        Plan(serve=sv)
    with pytest.raises(ValueError, match="all must be >= 1"):
        Plan(arch=cfg, serve=ServeSpec(gen=0))
    with pytest.raises(ValueError, match="temperature"):
        Plan(arch=cfg, serve=ServeSpec(temperature=-0.5))
    with pytest.raises(ValueError, match="cache_dtype"):
        Plan(arch=cfg, serve=ServeSpec(cache_dtype="fp4"))
    # serve shapes are frozen in the ServeSpec, not Plan.shape
    with pytest.raises(ValueError, match="drop Plan.shape"):
        Plan(arch=cfg, serve=sv, shape=ShapeConfig("x", 8, 2, "prefill"))
    # serving runs no gradient sync
    with pytest.raises(ValueError, match="no gradient synchronization"):
        Plan(arch=cfg, serve=sv, sync=BSP())
    with pytest.raises(ValueError, match="no gradient synchronization"):
        Plan(arch=cfg, serve=sv, sync=WSP(D=2))
    # train-only knobs the serve path would silently drop
    with pytest.raises(ValueError, match="no optimizer state"):
        Plan(arch=cfg, serve=sv, run=RunSpec(ckpt_dir="/tmp/x"))
    with pytest.raises(ValueError, match="moves KV cache"):
        Plan(arch=cfg, serve=sv, run=RunSpec(codec="topk:0.25"))
    with pytest.raises(ValueError, match="batches requests"):
        Plan(arch=cfg, serve=sv, cluster=ClusterSpec(num_vw=2))
    # cluster.topology alone is legal on serve Plans now: it prices the
    # Router's dispatch (see repro.serve.router)
    Plan(arch=cfg, serve=sv, cluster=ClusterSpec(topology="2node"))
    with pytest.raises(ValueError, match="unknown topology"):
        Plan(arch=cfg, serve=sv, cluster=ClusterSpec(topology="bogus"))
    # spmd serve keeps the whole batch on the model mesh
    with pytest.raises(ValueError, match="data-parallel serve"):
        Plan(arch=cfg, serve=sv, run=RunSpec(backend="spmd"),
             partition=PartitionSpec(stages=2, tp=1, data=2, devices=4))
    # and the reverse: serving shapes on a train Plan stay rejected
    with pytest.raises(ValueError, match="serving\\s+shape"):
        Plan(arch=cfg, shape=ShapeConfig("x", 64, 8, "decode"),
             run=RunSpec(backend="spmd", batch=8, seq=64),
             partition=PartitionSpec(stages=2, tp=1, data=1, devices=2))


def test_unreduced_serve_plan_builds_without_materializing():
    """Published widths get through the serve entry points: the training-
    only microbatch packing check does not apply to a serve Plan (qwen3's
    16 microbatches vs 8 decode slots), building the Engine allocates
    nothing, and the CLI has a switch for the full config."""
    from repro.launch.serve import build_parser
    arch = ARCHS["qwen3-0.6b"]
    plan = Plan(arch=arch, serve=ServeSpec(
        prompt_len=512, gen=32, max_batch=8, page_size=128,
        kernel_backend="tpu"))
    eng = Engine(plan)
    assert eng._params is None and eng._serve is None
    assert plan.arch.d_model == 1024 and plan.arch.num_layers == 28
    with pytest.raises(ValueError, match="not divisible by num_microbatches"):
        Plan(arch=arch, run=RunSpec(batch=8))       # training still checks
    assert build_parser().parse_args(["--full"]).reduced is False
    assert build_parser().parse_args([]).reduced is True


def test_engine_surface_refuses_mismatched_plans():
    cfg = _cfg("qwen3-0.6b")
    serve_plan = Plan(arch=cfg, serve=ServeSpec(prompt_len=8, gen=4,
                                                max_batch=2))
    train_plan = Plan(arch=cfg, run=RunSpec(max_waves=1, batch=4, seq=16))
    with pytest.raises(ValueError, match="generate"):
        Engine(serve_plan).fit()
    with pytest.raises(ValueError, match="prefill"):
        Engine(serve_plan).step()
    eng = Engine(train_plan)
    with pytest.raises(ValueError, match="Plan.serve is unset"):
        eng.generate()
    with pytest.raises(ValueError, match="Plan.serve is unset"):
        eng.prefill(np.zeros((2, 8), np.int32))
    with pytest.raises(ValueError, match="Plan.serve is unset"):
        eng.decode(np.zeros((2, 1), np.int32), None, 0)
    with pytest.raises(ValueError, match="Plan.serve is unset"):
        Scheduler(eng)


def test_prefill_rejects_wrong_shapes():
    cfg = _cfg("qwen3-0.6b")
    eng = Engine(Plan(arch=cfg, serve=ServeSpec(prompt_len=8, gen=4,
                                                max_batch=2)))
    with pytest.raises(ValueError, match="frozen serve shapes"):
        eng.prefill(np.zeros((2, 9), np.int32))
    with pytest.raises(ValueError, match="frozen serve shapes"):
        eng.prefill(np.zeros((3, 8), np.int32))


# ---------------------------------------------------------------------------
# generate() parity with the forward_ref oracle (greedy, bit-identical)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch,seed", _PARITY_CASES)
def test_generate_matches_forward_ref_greedy(arch, seed):
    """Engine.generate() on the threads backend must reproduce a hand-rolled
    forward_ref prefill + greedy decode loop token for token."""
    cfg = _cfg(arch)
    sv = ServeSpec(prompt_len=8, gen=5, max_batch=2)
    prompts = _prompts(cfg, seed, sv.max_batch, sv.prompt_len)
    rep = Engine(Plan(arch=cfg, serve=sv)).generate(prompts)

    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    cache = lm.init_cache(cfg, sv.max_batch, sv.max_len, dtype=jnp.float32)
    hid, cache, _ = lm.forward_ref(cfg, params, prompts, mode="prefill",
                                   cache=cache)
    tok = jnp.argmax(lm.logits_ref(cfg, params, hid[:, -1:])[:, -1], axis=-1)
    toks = [tok]
    for t in range(1, sv.gen):
        hid, cache, _ = lm.forward_ref(cfg, params, toks[-1][:, None],
                                       mode="decode", cache=cache,
                                       pos=jnp.int32(sv.prompt_len + t - 1))
        toks.append(jnp.argmax(lm.logits_ref(cfg, params, hid)[:, -1],
                               axis=-1))
    ref = np.stack([np.asarray(t) for t in toks], axis=1)
    np.testing.assert_array_equal(rep.tokens, ref)


@pytest.mark.parametrize("arch,seed", _PARITY_CASES)
def test_generate_spmd_matches_ref_backend(arch, seed):
    """The pipelined serve steps on a 1x1x1 mesh (single CPU device) must
    produce bit-identical greedy tokens to the forward_ref backend — same
    Plan, only run.backend differs (the deeper 2-stage/2-tp mesh parity
    runs in the subprocess harness below)."""
    cfg = _cfg(arch, stages=1, tp=1)
    sv = ServeSpec(prompt_len=8, gen=4, max_batch=2)
    prompts = _prompts(cfg, seed, sv.max_batch, sv.prompt_len)
    rep_ref = Engine(Plan(arch=cfg, serve=sv)).generate(prompts)
    rep_spmd = Engine(Plan(arch=cfg, serve=sv,
                           partition=PartitionSpec(stages=1, tp=1, data=1),
                           run=RunSpec(backend="spmd"))).generate(prompts)
    np.testing.assert_array_equal(rep_spmd.tokens, rep_ref.tokens)
    assert rep_spmd.backend == "spmd" and rep_ref.backend == "threads"


def test_generate_sampled_is_seeded():
    """temperature > 0 samples; the stream is deterministic in sample_seed
    and in range."""
    cfg = _cfg("qwen3-0.6b")
    sv = ServeSpec(prompt_len=8, gen=4, max_batch=2, temperature=1.0,
                   sample_seed=7)
    a = Engine(Plan(arch=cfg, serve=sv)).generate()
    b = Engine(Plan(arch=cfg, serve=sv)).generate()
    np.testing.assert_array_equal(a.tokens, b.tokens)
    assert a.tokens.min() >= 0 and a.tokens.max() < cfg.vocab_size
    c = Engine(Plan(arch=cfg, serve=ServeSpec(
        prompt_len=8, gen=4, max_batch=2, temperature=1.0,
        sample_seed=8))).generate()
    assert not np.array_equal(a.tokens, c.tokens)


def test_generate_frontend_arch_routes_embeddings():
    """Stub-frontend archs serve through synthesized frame embeddings (the
    old launch/serve.py fed raw token ids into the decode path)."""
    cfg = _cfg("musicgen-medium")
    assert cfg.frontend != "none"
    sv = ServeSpec(prompt_len=8, gen=3, max_batch=2)
    rep = Engine(Plan(arch=cfg, serve=sv)).generate()
    assert rep.tokens.shape == (2, 3)
    assert rep.tokens.min() >= 0 and rep.tokens.max() < cfg.vocab_size
    # the scheduler feeds ids back, which stub frontends cannot embed
    with pytest.raises(ValueError, match="stub-frontend"):
        Scheduler(Engine(Plan(arch=cfg, serve=sv)))


# ---------------------------------------------------------------------------
# continuous-batching scheduler invariants
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed,max_batch,n_req", _SCHED_CASES)
def test_scheduler_invariants(seed, max_batch, n_req):
    """FIFO admission (no request starves), retired slots are reused, and
    ServeReport token counts reconcile with the requests admitted."""
    cfg = _cfg("qwen3-0.6b")
    gen = 6
    rng = np.random.default_rng(seed)
    plan = Plan(arch=cfg, serve=ServeSpec(prompt_len=8, gen=gen,
                                          max_batch=max_batch))
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 8,
                                        dtype=np.int32),
                    max_new_tokens=int(rng.integers(1, gen + 1)))
            for i in range(n_req)]
    rep = Scheduler(Engine(plan)).run(list(reqs))
    # every request completed with exactly its budget
    assert sorted(r.rid for r in rep.requests) == list(range(n_req))
    for r, stats in zip(reqs, rep.requests):
        assert stats.new_tokens == (r.max_new_tokens or gen)
        assert 0 <= stats.slot < max_batch
        assert stats.finished_step >= stats.admitted_step
    # token counts reconcile
    assert rep.tokens_out == sum(r.max_new_tokens or gen for r in reqs)
    assert rep.slot_steps <= rep.decode_steps * max_batch
    # FIFO: admission order follows request order (no starvation)
    admits = [s.admitted_step for s in rep.requests]
    assert admits == sorted(admits)
    # slot reuse: more requests than slots forces a retired slot back in
    if n_req > max_batch:
        slots = [s.slot for s in rep.requests]
        assert len(set(slots)) < len(slots)
    occ = rep.occupancy()
    assert occ is not None and 0 < occ <= 1


def test_scheduler_co_batched_outputs_independent():
    """A request's tokens must not depend on its co-batched neighbors:
    batch-of-1 (max_batch=1 Plan) and batched (max_batch=3) runs produce
    bit-identical per-request streams, and the same holds within one
    compiled shape when neighbors differ."""
    cfg = _cfg("qwen3-0.6b")
    rng = np.random.default_rng(101)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 8,
                                        dtype=np.int32))
            for i in range(3)]
    big = Plan(arch=cfg, serve=ServeSpec(prompt_len=8, gen=5, max_batch=3))
    one = Plan(arch=cfg, serve=ServeSpec(prompt_len=8, gen=5, max_batch=1))
    batched = Scheduler(Engine(big)).run(list(reqs))
    for r, stats in zip(reqs, batched.requests):
        alone = Scheduler(Engine(one)).run([r])
        assert alone.requests[0].tokens == stats.tokens
    # same compiled shape, different neighbors: rid 0 alone in the batch
    solo = Scheduler(Engine(big)).run([reqs[0]])
    assert solo.requests[0].tokens == batched.requests[0].tokens


def test_decode_row_logits_independent_of_neighbors():
    """Engine.decode row values are bitwise independent of other rows (the
    property the scheduler's slot isolation rests on)."""
    cfg = _cfg("qwen3-0.6b")
    sv = ServeSpec(prompt_len=8, gen=4, max_batch=2)
    eng = Engine(Plan(arch=cfg, serve=sv))
    prompts = _prompts(cfg, 55, 2, 8)
    _, cache = eng.prefill(prompts)
    toks = np.array([[3], [200]], np.int32)
    pos = np.array([8, 8], np.int32)
    lg_a, _ = eng.decode(toks, cache, pos)
    # perturb row 1's token and position; row 0 must not move a bit
    toks_b = np.array([[3], [77]], np.int32)
    pos_b = np.array([8, 9], np.int32)
    lg_b, _ = eng.decode(toks_b, cache, pos_b)
    np.testing.assert_array_equal(np.asarray(lg_a)[0], np.asarray(lg_b)[0])
    assert not np.array_equal(np.asarray(lg_a)[1], np.asarray(lg_b)[1])


def test_scheduler_rejects_oversized_requests():
    cfg = _cfg("qwen3-0.6b")
    plan = Plan(arch=cfg, serve=ServeSpec(prompt_len=8, gen=4, max_batch=2))
    sch = Scheduler(Engine(plan))
    with pytest.raises(ValueError, match="frozen in the Plan"):
        sch.run([Request(rid=0, prompt=np.zeros(9, np.int32))])
    with pytest.raises(ValueError, match="must be in"):
        sch.run([Request(rid=0, prompt=np.zeros(8, np.int32),
                         max_new_tokens=5)])
    with pytest.raises(ValueError, match="must be in"):
        sch.run([Request(rid=0, prompt=np.zeros(8, np.int32),
                         max_new_tokens=-2)])


# ---------------------------------------------------------------------------
# presets
# ---------------------------------------------------------------------------
def test_serve_presets_build_and_run():
    plan = get_preset("serve_tiny", serve__gen=3)
    assert plan.serve is not None and plan.serve.gen == 3
    rep = Engine(plan).generate()
    assert rep.tokens.shape == (plan.serve.max_batch, 3)
    spmd = get_preset("serve_spmd")
    assert spmd.run.backend == "spmd" and spmd.serve is not None


# ---------------------------------------------------------------------------
# subprocess: parity on a real (1, 2, 2) pipelined mesh
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch,seed", _PARITY_CASES)
def test_serve_parity_on_pipelined_mesh(arch, seed):
    """build_prefill_step/build_decode_step (and Engine.generate / the
    Scheduler on top of them) must match the forward_ref cache path on a
    2-stage, 2-tp mesh — logits to tolerance, greedy tokens bit-identical."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "serve_parity_main.py"),
         arch, str(seed)],
        capture_output=True, text=True, timeout=1200, env=env)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "generate_tokens_identical=1" in r.stdout
    assert "scheduler_tokens_identical=1" in r.stdout
    assert "paged_scheduler_tokens_identical=1" in r.stdout
    assert "shared_prefix_tokens_identical=1" in r.stdout
    assert "kernel_backend_tokens_identical=1" in r.stdout
