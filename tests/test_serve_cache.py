"""The paged cache subsystem (repro.serve.cache): CacheStore accounting,
paged-vs-contiguous parity, variable-length prompts vs unpadded ground
truth, per-request page budgets, fp8 KV through the paged path, the
deadline admission policy, and the Pallas flash-decode kernel over a
gathered-page layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Engine, Plan, ServeSpec
from repro.api.serving import Request, Scheduler
from repro.configs import ARCHS, reduced
from repro.models import lm
from repro.serve import cache as cache_lib
from repro.serve.cache import CacheStore, make_layout

SERVE_ARCHS = ("qwen3-0.6b", "h2o-danube-1.8b", "rwkv6-3b")

_R = np.random.default_rng(23)
_FAMILY_CASES = [(a, int(_R.integers(0, 1_000))) for a in SERVE_ARCHS]


def _cfg(name: str, **over):
    base = dict(num_layers=2, d_model=32, d_ff=64, vocab_size=256,
                num_microbatches=2)
    if ARCHS[name].attn_type == "swa":
        base["window_size"] = 6        # < max_len: exercise the ring wrap
    base.update(over)
    return reduced(ARCHS[name], **base)


def _reqs(cfg, seed, n, plen, budgets=None, lens=None):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        L = plen if lens is None else lens[i]
        out.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, L, dtype=np.int32),
            max_new_tokens=0 if budgets is None else budgets[i]))
    return out


# ---------------------------------------------------------------------------
# CacheStore accounting
# ---------------------------------------------------------------------------
def test_layout_geometry_and_validation():
    lo = make_layout(4, 24, page_size=8)
    assert (lo.pages_per_slot, lo.num_pages, lo.trash_page) == (3, 12, 12)
    assert lo.pages_for(1) == 1 and lo.pages_for(8) == 1
    assert lo.pages_for(9) == 2 and lo.pages_for(24) == 3
    # degenerate: one page per slot
    lo = make_layout(4, 24)
    assert (lo.page_size, lo.pages_per_slot, lo.num_pages) == (24, 1, 4)
    with pytest.raises(ValueError, match="outside"):
        make_layout(4, 24, page_size=25)
    with pytest.raises(ValueError, match="worst-case request"):
        make_layout(4, 24, page_size=8, max_pages=2)


def test_store_alloc_free_accounting():
    cfg = _cfg("qwen3-0.6b")
    store = CacheStore(cfg, make_layout(2, 16, page_size=4),
                       dtype=jnp.float32)
    assert store.stats()["pages_total"] == 8
    assert store.can_alloc(16)
    store.alloc(0, 10)                       # 3 pages
    assert store.pages_in_use == 3
    with pytest.raises(ValueError, match="already holds"):
        store.alloc(0, 4)
    store.alloc(1, 16)                       # 4 pages
    assert store.pages_in_use == 7 and not store.can_alloc(8)
    with pytest.raises(RuntimeError, match="exhausted"):
        store.alloc(2, 8)
    tab = np.asarray(store.tree["block_tab"])
    assert (tab[0] >= 0).sum() == 3 and (tab[1] >= 0).sum() == 4
    store.free(0)
    assert store.pages_in_use == 4 and store.can_alloc(16)
    assert np.all(np.asarray(store.tree["block_tab"])[0] == -1)
    store.free(0)                            # idempotent
    assert store.peak_pages == 7
    with pytest.raises(ValueError, match="exceed max_len"):
        store.alloc(0, 17)
    s = store.stats()
    assert s["pages_in_use"] + s["pages_free"] == s["pages_total"]
    assert s["pool_bytes"] == s["page_bytes"] * s["pages_total"]


# ---------------------------------------------------------------------------
# paged vs contiguous parity (page_size < prompt_len)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch,seed", _FAMILY_CASES)
def test_paged_scheduler_matches_contiguous(arch, seed):
    """With page_size < prompt_len every slot's KV is split across pages;
    per-request token streams must match the contiguous degenerate bit for
    bit (greedy)."""
    cfg = _cfg(arch)
    rng = np.random.default_rng(seed)
    budgets = [int(rng.integers(1, 7)) for _ in range(6)]
    reqs = _reqs(cfg, seed, 6, 8, budgets=budgets)
    base = Plan(arch=cfg, serve=ServeSpec(prompt_len=8, gen=6, max_batch=2))
    paged = base.replace(serve=ServeSpec(prompt_len=8, gen=6, max_batch=2,
                                         page_size=4))
    out_c = Scheduler(Engine(base)).run([Request(r.rid, r.prompt.copy(),
                                                 r.max_new_tokens)
                                         for r in reqs])
    out_p = Scheduler(Engine(paged)).run(reqs)
    for a, b in zip(out_c.requests, out_p.requests):
        assert a.rid == b.rid and a.tokens == b.tokens
    if cfg.attn_type == "full":
        assert out_p.pages_total == 8  # ceil((8+6)/4) pages/slot x 2 slots
        assert out_p.peak_pages <= out_p.pages_total
        assert out_p.page_utilization() is not None
    else:
        # no full-attention KV group -> no pool to ration: admission must
        # never block on phantom pages
        assert out_p.pages_total == 0 and out_p.admit_blocked == 0
    assert out_p.page_size == 4


@pytest.mark.parametrize("arch,seed", _FAMILY_CASES)
def test_varlen_prompts_match_unpadded_reference(arch, seed):
    """Variable-length admissions (right-padded prompts + per-row lens)
    must reproduce, per request, the tokens of that request served alone
    with an exactly-sized contiguous cache — across all three families
    (KV masking, ring-buffer masking, SSM/RWKV state no-op on pads)."""
    cfg = _cfg(arch)
    P, G = 8, 4
    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(2, P + 1)) for _ in range(4)]
    reqs = _reqs(cfg, seed, 4, P, lens=lens)
    plan = Plan(arch=cfg, serve=ServeSpec(prompt_len=P, gen=G, max_batch=2,
                                          page_size=4))
    rep = Scheduler(Engine(plan)).run([Request(r.rid, r.prompt.copy())
                                       for r in reqs])
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    for r, stats in zip(reqs, rep.requests):
        L = len(r.prompt)
        assert stats.prompt_len == L
        cache = lm.init_cache(cfg, 1, L + G, dtype=jnp.float32)
        hid, cache, _ = lm.forward_ref(cfg, params, jnp.asarray(r.prompt)[None],
                                       mode="prefill", cache=cache)
        tok = int(jnp.argmax(lm.logits_ref(cfg, params, hid[:, -1:])[0, -1]))
        want = [tok]
        for t in range(1, G):
            hid, cache, _ = lm.forward_ref(
                cfg, params, jnp.asarray([[want[-1]]], jnp.int32),
                mode="decode", cache=cache, pos=jnp.int32(L + t - 1))
            want.append(int(jnp.argmax(lm.logits_ref(cfg, params,
                                                     hid)[0, -1])))
        assert stats.tokens == want, (r.rid, L, stats.tokens, want)


def test_prompt_length_validation():
    cfg = _cfg("qwen3-0.6b")
    sch = Scheduler(Engine(Plan(arch=cfg, serve=ServeSpec(prompt_len=8,
                                                          gen=4,
                                                          max_batch=2))))
    with pytest.raises(ValueError, match="frozen in the Plan"):
        sch.run([Request(rid=0, prompt=np.zeros(9, np.int32))])
    with pytest.raises(ValueError, match="frozen in the Plan"):
        sch.run([Request(rid=0, prompt=np.zeros(0, np.int32))])


# ---------------------------------------------------------------------------
# per-request page budgets (no worst-case reservation)
# ---------------------------------------------------------------------------
def test_mixed_budgets_allocate_fewer_pages_than_worst_case():
    """Request.max_new_tokens sizes each slot's pages by its own budget:
    a mixed-budget batch must peak below the uniform worst case."""
    cfg = _cfg("qwen3-0.6b")
    sv = ServeSpec(prompt_len=8, gen=8, max_batch=2, page_size=4)
    uniform = _reqs(cfg, 5, 4, 8)                       # budget = gen = 8
    mixed = _reqs(cfg, 5, 4, 8, budgets=[2, 1, 2, 1])
    rep_u = Scheduler(Engine(Plan(arch=cfg, serve=sv))).run(uniform)
    rep_m = Scheduler(Engine(Plan(arch=cfg, serve=sv))).run(mixed)
    # worst case: ceil((8+8)/4) = 4 pages x 2 slots in flight
    assert rep_u.peak_pages == 8
    # mixed: ceil((8+2)/4) = 3 pages at most per slot
    assert rep_m.peak_pages <= 6 < rep_u.peak_pages


def test_admission_refused_when_pool_exhausted():
    """A free batch slot is not enough: admission waits for pages. With a
    pool sized for one worst-case request, requests serialize (and the
    blocked rounds are counted) but all complete."""
    cfg = _cfg("qwen3-0.6b")
    sv = ServeSpec(prompt_len=8, gen=8, max_batch=2, page_size=4,
                   max_pages=4)
    reqs = _reqs(cfg, 9, 3, 8)                          # 4 pages each
    rep = Scheduler(Engine(Plan(arch=cfg, serve=sv))).run(reqs)
    assert sorted(r.rid for r in rep.requests) == [0, 1, 2]
    assert all(r.new_tokens == sv.gen for r in rep.requests)
    assert rep.admit_blocked > 0
    assert rep.peak_pages <= 4
    # pool-serialized: admissions cannot overlap
    admits = sorted(r.admitted_step for r in rep.requests)
    assert admits[1] > admits[0] and admits[2] > admits[1]


# ---------------------------------------------------------------------------
# fp8 KV through the paged path
# ---------------------------------------------------------------------------
def test_fp8_paged_scheduler_end_to_end():
    """cache_dtype='f8' through the paged Scheduler path: completes, and
    produces the same streams as fp8 over the contiguous degenerate (the
    quantization, not the layout, decides the tokens)."""
    cfg = _cfg("qwen3-0.6b")
    reqs = _reqs(cfg, 11, 4, 8, budgets=[3, 5, 2, 4])
    f8 = dict(prompt_len=8, gen=6, max_batch=2, cache_dtype="f8")
    rep_p = Scheduler(Engine(Plan(arch=cfg, serve=ServeSpec(
        page_size=4, **f8)))).run([Request(r.rid, r.prompt.copy(),
                                           r.max_new_tokens) for r in reqs])
    rep_c = Scheduler(Engine(Plan(arch=cfg, serve=ServeSpec(**f8)))).run(reqs)
    for a, b in zip(rep_p.requests, rep_c.requests):
        assert a.rid == b.rid and a.tokens == b.tokens
    assert rep_p.tokens_out == sum(r.max_new_tokens for r in reqs)


def test_fp8_halves_page_bytes():
    """CacheStore.stats(): fp8 pages are half the bytes of bf16 pages of
    the same geometry."""
    cfg = _cfg("qwen3-0.6b")
    lo = make_layout(2, 16, page_size=4)
    _, bf16 = cache_lib.serve_dtypes("bfloat16", "")
    _, f8 = cache_lib.serve_dtypes("bfloat16", "f8")
    s_bf16 = CacheStore(cfg, lo, dtype=bf16).stats()
    s_f8 = CacheStore(cfg, lo, dtype=f8).stats()
    assert s_f8["page_bytes"] * 2 == s_bf16["page_bytes"] > 0
    assert s_f8["pool_bytes"] * 2 == s_bf16["pool_bytes"]


# ---------------------------------------------------------------------------
# deadline admission policy
# ---------------------------------------------------------------------------
def test_deadline_policy_orders_by_slack():
    """With one decode slot, the deadline policy admits the tightest-slack
    request first; FIFO admits in arrival order."""
    cfg = _cfg("qwen3-0.6b")
    plan = Plan(arch=cfg, serve=ServeSpec(prompt_len=8, gen=4, max_batch=1))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, 8, dtype=np.int32) for _ in range(3)]
    mk = lambda: [Request(rid=0, prompt=prompts[0], max_new_tokens=2),
                  Request(rid=1, prompt=prompts[1], max_new_tokens=2,
                          deadline=100),
                  Request(rid=2, prompt=prompts[2], max_new_tokens=2,
                          deadline=3)]
    fifo = Scheduler(Engine(plan), policy="fifo").run(mk())
    edf = Scheduler(Engine(plan), policy="deadline").run(mk())
    order_f = [r.rid for r in sorted(fifo.requests,
                                     key=lambda r: r.admitted_step)]
    order_e = [r.rid for r in sorted(edf.requests,
                                     key=lambda r: r.admitted_step)]
    assert order_f == [0, 1, 2]
    # rid 2 (slack 3-2=1) < rid 1 (slack 98) < rid 0 (no deadline, inf)
    assert order_e == [2, 1, 0]
    # a request's tokens never depend on admission order
    for a in fifo.requests:
        b = next(r for r in edf.requests if r.rid == a.rid)
        assert a.tokens == b.tokens


def test_deadline_policy_fifo_among_slack_ties():
    """Equal slack (including all-no-deadline) must keep strict arrival
    order — the no-starvation invariant."""
    cfg = _cfg("qwen3-0.6b")
    plan = Plan(arch=cfg, serve=ServeSpec(prompt_len=8, gen=4, max_batch=1))
    rng = np.random.default_rng(4)
    no_dl = [Request(rid=i, prompt=rng.integers(0, 256, 8, dtype=np.int32),
                     max_new_tokens=2) for i in range(4)]
    rep = Scheduler(Engine(plan), policy="deadline").run(no_dl)
    order = [r.rid for r in sorted(rep.requests,
                                   key=lambda r: r.admitted_step)]
    assert order == [0, 1, 2, 3]
    same_dl = [Request(rid=i, prompt=rng.integers(0, 256, 8, dtype=np.int32),
                       max_new_tokens=2, deadline=50) for i in range(4)]
    rep = Scheduler(Engine(plan), policy="deadline").run(same_dl)
    order = [r.rid for r in sorted(rep.requests,
                                   key=lambda r: r.admitted_step)]
    assert order == [0, 1, 2, 3]


def test_policy_validation():
    cfg = _cfg("qwen3-0.6b")
    plan = Plan(arch=cfg, serve=ServeSpec(prompt_len=8, gen=4, max_batch=1))
    with pytest.raises(ValueError, match="unknown admission policy"):
        Scheduler(Engine(plan), policy="lifo")
    sch = Scheduler(Engine(plan))
    with pytest.raises(ValueError, match="deadline"):
        sch.run([Request(rid=0, prompt=np.zeros(8, np.int32), deadline=-1)])


# ---------------------------------------------------------------------------
# ServeSpec page knobs
# ---------------------------------------------------------------------------
def test_serve_spec_page_validation():
    cfg = _cfg("qwen3-0.6b")
    with pytest.raises(ValueError, match="must be >= 0"):
        Plan(arch=cfg, serve=ServeSpec(page_size=-1))
    with pytest.raises(ValueError, match="outside"):
        Plan(arch=cfg, serve=ServeSpec(prompt_len=8, gen=4, page_size=16))
    with pytest.raises(ValueError, match="worst-case request"):
        Plan(arch=cfg, serve=ServeSpec(prompt_len=8, gen=8, page_size=4,
                                       max_pages=3))


# ---------------------------------------------------------------------------
# Pallas flash-decode over the gathered-page layout (interpret mode)
# ---------------------------------------------------------------------------
def test_flash_decode_gathered_pages_matches_contiguous():
    """Scatter a contiguous KV cache into a paged pool through a permuted
    block table, gather it back per row, and run the Pallas flash-decode
    kernel on the gathered view: bitwise identical (atol=0) to the kernel
    over the original contiguous layout."""
    from repro.kernels.flash_decode import flash_decode
    B, KV, G, S, hd, ps = 2, 2, 2, 32, 16, 8
    H = KV * G
    lo = make_layout(B, S, page_size=ps)
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
    length = S - 3

    # pool with a deliberately permuted page assignment
    pages = rng.permutation(lo.num_pages).reshape(B, lo.pages_per_slot)
    tab = jnp.asarray(pages, jnp.int32)
    pool_shape = (1, lo.num_pages + 1, KV, ps, hd)
    pool_k = jnp.zeros(pool_shape, jnp.float32)
    pool_v = jnp.zeros(pool_shape, jnp.float32)
    sel = jnp.ones((B,), bool)
    pool_k = cache_lib.page_write_prompt(pool_k, 0, tab, k, sel)
    pool_v = cache_lib.page_write_prompt(pool_v, 0, tab, v, sel)
    k_view, gpos = cache_lib.page_view(pool_k, 0, tab)
    v_view, _ = cache_lib.page_view(pool_v, 0, tab)
    # the gather must reproduce the contiguous layout exactly
    np.testing.assert_array_equal(np.asarray(k_view), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(gpos),
                                  np.tile(np.arange(S), (B, 1)))

    to_kernel = lambda a: jnp.transpose(a, (0, 2, 1, 3))   # [B, KV, S, hd]
    out_pages = flash_decode(q, to_kernel(k_view), to_kernel(v_view),
                             length, block_k=ps, interpret=True)
    out_contig = flash_decode(q, to_kernel(k), to_kernel(v), length,
                              block_k=ps, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_pages),
                                  np.asarray(out_contig))
    # and the decode_attend jnp reference agrees on the same view
    from repro.models.attention import decode_attend
    ref = decode_attend(q[:, None], k_view, v_view, gpos,
                        jnp.int32(length - 1))
    np.testing.assert_allclose(np.asarray(ref[:, 0]), np.asarray(out_pages),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_paged_pool_direct_matches_gather_view():
    """The fused kernel consumes the CacheStore pool + block table
    *directly* (the block-table walk lives in the BlockSpec index map) and
    matches the materialize-then-decode path it replaces, at mixed per-row
    depths with unmapped tail pages."""
    from repro.kernels.flash_decode import flash_decode, flash_decode_paged
    B, KV, G, S, hd, ps = 3, 2, 2, 32, 16, 8
    H = KV * G
    lo = make_layout(B, S, page_size=ps)
    rng = np.random.default_rng(5)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
    # staggered depths: full row, mid-page row, empty row
    lens = jnp.asarray([S, ps + 3, 0], jnp.int32)

    pages = rng.permutation(lo.num_pages).reshape(B, lo.pages_per_slot)
    tab = jnp.asarray(pages, jnp.int32)
    # rows only own the pages their depth needs; the rest are unmapped
    tab = tab.at[1, 2:].set(-1)
    tab = tab.at[2, :].set(-1)
    pool_shape = (1, lo.num_pages + 1, KV, ps, hd)
    pool_k = cache_lib.page_write_prompt(jnp.zeros(pool_shape), 0, tab, k,
                                         jnp.ones((B,), bool))
    pool_v = cache_lib.page_write_prompt(jnp.zeros(pool_shape), 0, tab, v,
                                         jnp.ones((B,), bool))

    out = flash_decode_paged(q, pool_k, pool_v, tab, lens, layer=0,
                             interpret=True)
    # the replaced path: gather a contiguous view, then contiguous kernel
    k_view, _ = cache_lib.page_view(pool_k, 0, tab)
    v_view, _ = cache_lib.page_view(pool_v, 0, tab)
    to_kernel = lambda a: jnp.transpose(a, (0, 2, 1, 3))
    via_view = flash_decode(q, to_kernel(k_view), to_kernel(v_view), lens,
                            block_k=ps, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(via_view),
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(out[2]) == 0.0)            # empty row: zeros
    # and the jnp paged oracle agrees
    from repro.kernels import ref as kref
    ref = kref.decode_paged_ref(q, pool_k, pool_v, tab, lens, layer=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("arch,seed", _FAMILY_CASES,
                         ids=[a for a, _ in _FAMILY_CASES])
def test_scheduler_kernel_backend_stream_parity(arch, seed):
    """Satellite of the kernel-backend wiring: continuous-batching token
    streams with slots at different depths are bit-identical between the
    Pallas kernels (interpret mode; paged decode reads the pool through
    the block table with per-row lengths) and the jnp "ref" oracle, for
    every serve family."""
    cfg = _cfg(arch)
    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(2, 9)) for _ in range(6)]
    budgets = [int(rng.integers(1, 6)) for _ in range(6)]

    def run(kb):
        plan = Plan(arch=cfg, serve=ServeSpec(
            prompt_len=8, gen=6, max_batch=2, page_size=4,
            kernel_backend=kb))
        reqs = _reqs(cfg, seed, 6, 8, budgets=budgets, lens=lens)
        return [r.tokens for r in Scheduler(Engine(plan)).run(reqs).requests]

    assert run("ref") == run("interpret")


def test_scheduler_kernel_backend_fp8_stream_parity():
    """fp8 KV pages quantize identically under both backends: the paged
    kernel reads the pool pages as stored and casts in-register."""
    cfg = _cfg("qwen3-0.6b")

    def run(kb):
        plan = Plan(arch=cfg, serve=ServeSpec(
            prompt_len=8, gen=6, max_batch=2, page_size=4, cache_dtype="f8",
            kernel_backend=kb))
        reqs = _reqs(cfg, 11, 6, 8, budgets=[3] * 6,
                     lens=[3, 8, 5, 2, 7, 4])
        return [r.tokens for r in Scheduler(Engine(plan)).run(reqs).requests]

    assert run("ref") == run("interpret")


@pytest.mark.parametrize("kb", ["ref", "interpret"])
def test_paged_decode_logits_match_contiguous(kb):
    """Prefill + decode through the paged pool reproduces the contiguous
    cache's logits at positions past the first pages: each decode token
    lands in its own page (a capacity miscounted from the pool's axes
    would send it to the trash page instead)."""
    cfg = _cfg("qwen3-0.6b")
    B, P, gen, ps = 3, 20, 3, 8
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(4))
    prompts = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab_size, (B, P)), jnp.int32)

    def engine(page_size):
        return Engine(Plan(arch=cfg, serve=ServeSpec(
            prompt_len=P, gen=gen, max_batch=B, page_size=page_size,
            kernel_backend=kb)), params=params)

    contig = engine(0)
    want_pf, cache = contig.prefill(prompts)
    paged = engine(ps)
    store = paged.serve_store()
    for s in range(B):
        store.alloc(s, P + gen)
    got_pf = paged.prefill_into(store, prompts, np.full(B, P), list(range(B)))
    np.testing.assert_allclose(np.asarray(got_pf), np.asarray(want_pf),
                               rtol=1e-5, atol=1e-5)
    tok = jnp.argmax(want_pf, axis=-1).astype(jnp.int32)[:, None]
    for t in range(gen - 1):
        want, cache = contig.decode(tok, cache, P + t)
        got, _ = paged.decode(tok, store, np.full(B, P + t))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        tok = jnp.argmax(want, axis=-1).astype(jnp.int32)[:, None]


def test_page_write_token_routes_unmapped_to_trash():
    """Decode writes for unmapped rows land in the trash page, never in a
    live page; mapped rows land at (page, offset) of their position."""
    lo = make_layout(2, 8, page_size=4)
    pool = jnp.zeros((1, lo.num_pages + 1, 1, 4, 2), jnp.float32)
    tab = jnp.asarray([[0, 2], [-1, -1]], jnp.int32)
    row = jnp.ones((2, 1, 1, 2), jnp.float32)
    pos = jnp.asarray([5, 6], jnp.int32)
    out = cache_lib.page_write_token(pool, 0, tab, pos,
                                     row, jnp.asarray([True, True]))
    out = np.asarray(out)
    assert np.all(out[0, 2, :, 1] == 1.0)       # row 0: page 2, offset 1
    assert np.all(out[0, :lo.num_pages].sum() == 2.0)  # nothing else live
    assert np.all(out[0, lo.trash_page, :, 2] == 1.0)  # row 1 -> trash


def _write_token_oracle(pool, i, tab, pos, row, sel):
    """The decode write's semantics, one row at a time in numpy: row b's
    token lands at (page of pos[b], offset pos[b] % ps) of group i, or at
    that offset of the trash page when the row is unselected or its page
    unmapped. Returns (pool, {trash offset: rows routed there})."""
    out = np.array(pool)
    B, pps = tab.shape
    ps, trash = pool.shape[3], pool.shape[1] - 1
    to_trash = {}
    for b in range(B):
        phys = tab[b, min(max(pos[b] // ps, 0), pps - 1)]
        off = max(pos[b], 0) % ps
        if not sel[b] or phys < 0:
            to_trash.setdefault(off, []).append(b)
            continue
        out[i, phys, :, off] = row[b, 0]
    return out, to_trash


_WRITE_CASES = {
    # name: (block table rows, positions, selected rows)
    "mapped": ([[0, 1], [2, 3], [4, 5]], [0, 5, 7], [1, 1, 1]),
    "unselected": ([[0, 1], [2, 3], [4, 5]], [3, 4, 6], [1, 0, 1]),
    "unmapped": ([[0, -1], [-1, -1], [4, 5]], [6, 1, 3], [1, 1, 1]),
    "several_to_trash": ([[0, 1], [-1, -1], [4, -1]], [2, 2, 6], [0, 1, 1]),
    "page_edges": ([[5, 0], [1, 2], [3, 4]], [4, 3, 0], [1, 1, 1]),
}


@pytest.mark.parametrize("case", sorted(_WRITE_CASES))
def test_page_write_token_matches_scatter_oracle(case):
    """The per-row decode write matches the scatter it replaced, on mapped
    rows, unselected rows, unmapped pages, several rows sent to the trash
    page, and offsets 0 and page_size - 1 (page size 4, group 1 of 2).
    Only the trash page takes duplicate writes: each offset written there
    holds one of the rows routed to it, and every other slot of the pool
    equals the oracle's."""
    tab_rows, pos_l, sel_l = _WRITE_CASES[case]
    B, ps, KV, hd = 3, 4, 2, 3
    lo = make_layout(B, 8, page_size=ps)
    rng = np.random.default_rng(sorted(_WRITE_CASES).index(case))
    pool = rng.standard_normal((2, lo.num_pages + 1, KV, ps, hd)).astype(
        np.float32)
    row = rng.standard_normal((B, 1, KV, hd)).astype(np.float32)
    tab = np.asarray(tab_rows, np.int32)
    pos = np.asarray(pos_l, np.int32)
    sel = np.asarray(sel_l, bool)
    got = np.asarray(cache_lib.page_write_token(
        jnp.asarray(pool), 1, jnp.asarray(tab), jnp.asarray(pos),
        jnp.asarray(row), jnp.asarray(sel)))
    want, to_trash = _write_token_oracle(pool, 1, tab, pos, row, sel)
    trash = lo.trash_page
    np.testing.assert_array_equal(got[:, :trash], want[:, :trash])
    np.testing.assert_array_equal(got[0], want[0])
    for off in range(ps):
        held = got[1, trash, :, off]
        if off not in to_trash:
            np.testing.assert_array_equal(held, pool[1, trash, :, off])
        else:
            assert any(np.array_equal(held, row[b, 0])
                       for b in to_trash[off]), (off, to_trash[off])


@pytest.mark.parametrize("kb", ["ref", "interpret"])
def test_donated_pool_survives_decode_copy_prefill_and_gather(kb):
    """The paged decode step takes the page pool donated. Several decodes
    on one CacheStore, then a copy-on-write page copy, a prefill into a
    freed slot, a gathered view and one more decode: nothing reads a
    deleted buffer, the copied page equals its source, the gathered K
    equals the contiguous cache's, and every logit equals the contiguous
    path's for the same sequences (the re-admitted row replays its first
    decode step)."""
    cfg = _cfg("qwen3-0.6b")
    B, P, gen, ps = 3, 20, 6, 8
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(9))
    prompts = np.random.default_rng(9).integers(
        0, cfg.vocab_size, (B, P)).astype(np.int32)

    def engine(page_size):
        return Engine(Plan(arch=cfg, serve=ServeSpec(
            prompt_len=P, gen=gen, max_batch=B, page_size=page_size,
            kernel_backend=kb)), params=params)

    contig, paged = engine(0), engine(ps)
    want_pf, cache = contig.prefill(jnp.asarray(prompts))
    store = paged.serve_store()
    for s in range(B):
        store.alloc(s, P + gen)
    got_pf = paged.prefill_into(store, prompts, np.full(B, P), list(range(B)))
    np.testing.assert_allclose(np.asarray(got_pf), np.asarray(want_pf),
                               rtol=1e-5, atol=1e-5)
    first = np.argmax(np.asarray(want_pf), axis=-1).astype(np.int32)
    tok = first[:, None]
    steps = 3
    for t in range(steps):
        want, cache = contig.decode(tok, cache, P + t)
        held = store.tree["kv_full"]
        got, _ = paged.decode(tok, store, np.full(B, P + t))
        assert all(pool.is_deleted() for pool in held)   # donated
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        if t == 0:
            first_step = np.asarray(want)
        tok = np.argmax(np.asarray(want), axis=-1).astype(np.int32)[:, None]

    # copy-on-write into a page slot 2 gave back
    tab = np.asarray(store.tree["block_tab"])
    src, dst = int(tab[0, 0]), int(tab[2, 0])
    assert store.free(2) == []
    store.copy_page(src, dst)
    for pool in store.tree["kv_full"]:
        np.testing.assert_array_equal(np.asarray(pool[:, dst]),
                                      np.asarray(pool[:, src]))

    # slot 2 re-admits its prompt; rows 0 and 1 keep their pages
    store.alloc(2, P + gen)
    rows = np.zeros((B, P), np.int32)
    rows[0] = prompts[2]
    lens = np.ones(B, np.int32)
    lens[0] = P
    re_pf = paged.prefill_into(store, rows, lens, [2])
    np.testing.assert_allclose(np.asarray(re_pf[0]), np.asarray(want_pf[2]),
                               rtol=1e-5, atol=1e-5)

    k_view, v_view, gpos = store.gather_view(0)
    depth = P + steps
    for pool, view in zip(cache["kv_full"], (k_view, v_view)):
        np.testing.assert_allclose(np.asarray(view[:2, :depth]),
                                   np.asarray(pool[0, :2, :depth]),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(view[2, :P]),
                                   np.asarray(pool[0, 2, :P]),
                                   rtol=1e-6, atol=1e-6)
    assert np.all(np.asarray(gpos)[:, :depth] >= 0)

    tok[2, 0] = first[2]
    pos = np.asarray([depth, depth, P], np.int32)
    want, cache = contig.decode(tok, cache, pos)
    got, _ = paged.decode(tok, store, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[2]), first_step[2],
                               rtol=1e-5, atol=1e-5)
