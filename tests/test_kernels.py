"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.mamba_ssd import ssd_chunked
from repro.kernels.moe_gmm import grouped_matmul
from repro.kernels.rwkv6_scan import rwkv6_chunked

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 2, 2, 64, 16), (2, 4, 2, 128, 32), (1, 8, 1, 96, 64),
    (1, 4, 2, 72, 16),            # S not a block multiple: padded + masked
])
@pytest.mark.parametrize("window", [0, 40])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, KV, S, hd, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    ref = kref.attention_ref(q, k, v, causal=True, window=window)
    out = flash_attention_fwd(q, k, v, causal=True, window=window,
                              block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,H,KV,S,hd", [
    (2, 4, 2, 128, 32),           # GQA G=2
    (1, 6, 6, 64, 16),            # MHA: G == 1, H == KV
])
@pytest.mark.parametrize("length", [0, 1, 37, 64])
@pytest.mark.parametrize("window", [0, 48, 96])
def test_flash_decode(B, H, KV, S, hd, length, window):
    """Sweep covers the contract edges: length == 0 (zeros, not a uniform
    average over uninitialized V) and window >= length (full coverage,
    window mask inert)."""
    ks = jax.random.split(KEY, 3)
    q1 = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    ref = kref.decode_ref(q1, k, v, length, window=window)
    out = flash_decode(q1, k, v, length, window=window, block_k=32,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    if length == 0:
        assert np.all(np.asarray(out) == 0.0)
        assert np.all(np.asarray(ref) == 0.0)


def test_flash_decode_zero_length_ignores_uninitialized_v():
    """length == 0 emits exact zeros even when the unwritten cache holds
    garbage — the old oracle softmax averaged V uniformly instead."""
    B, H, KV, S, hd = 2, 4, 2, 32, 16
    q1 = jax.random.normal(KEY, (B, H, hd))
    k = jnp.full((B, KV, S, hd), 1e6)
    v = jnp.full((B, KV, S, hd), -1e6)
    assert np.all(np.asarray(kref.decode_ref(q1, k, v, 0)) == 0.0)
    assert np.all(np.asarray(
        flash_decode(q1, k, v, 0, block_k=16, interpret=True)) == 0.0)


def test_flash_decode_per_row_lengths():
    """A [B] int32 length vector masks each row at its own depth — the
    serve decode path's mixed-depth batches."""
    B, H, KV, S, hd = 4, 4, 2, 64, 16
    ks = jax.random.split(KEY, 3)
    q1 = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    lens = jnp.asarray([0, 1, 33, 64], jnp.int32)
    ref = kref.decode_ref(q1, k, v, lens)
    out = flash_decode(q1, k, v, lens, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    assert np.all(np.asarray(out[0]) == 0.0)            # length-0 row
    # each row matches a scalar-length call at its own depth
    for b, n in enumerate([0, 1, 33, 64]):
        one = flash_decode(q1[b:b + 1], k[b:b + 1], v[b:b + 1], n,
                           block_k=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(one[0]),
                                   atol=2e-5, rtol=2e-5)


def test_flash_decode_unaligned_cache_pads_not_degrades(caplog):
    """A prime cache length no longer silently degrades block_k to 1 —
    the KV view is padded to a block multiple (dead, masked) and the
    fallback is logged."""
    import logging
    B, H, KV, S, hd = 2, 4, 2, 37, 16
    ks = jax.random.split(KEY, 3)
    q1 = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    ref = kref.decode_ref(q1, k, v, 37)
    with caplog.at_level(logging.WARNING, logger="repro.kernels"):
        out = flash_decode(q1, k, v, 37, block_k=16, interpret=True)
    assert any("padding" in r.message for r in caplog.records)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.float8_e4m3fn])
def test_flash_decode_paged(pool_dtype):
    """The paged kernel walks the stacked pool [groups, pages+1, KV, ps,
    hd] through the block table inside the index map: unmapped (-1)
    entries route to the trash page, rows mask at their own length, and
    a length-0 row emits zeros."""
    L, P1, ps, B, KV, G, hd = 2, 9, 4, 3, 2, 2, 16
    H = KV * G
    ks = jax.random.split(KEY, 4)
    pool_k = jax.random.normal(ks[0], (L, P1, KV, ps, hd)).astype(pool_dtype)
    pool_v = jax.random.normal(ks[1], (L, P1, KV, ps, hd)).astype(pool_dtype)
    q1 = jax.random.normal(ks[2], (B, H, hd))
    tab = jnp.asarray([[0, 3, 6], [1, 4, -1], [-1, -1, -1]], jnp.int32)
    lens = jnp.asarray([11, 6, 0], jnp.int32)
    for layer in (0, 1):
        ref = kref.decode_paged_ref(q1, pool_k, pool_v, tab, lens,
                                    layer=layer)
        out = flash_decode_paged(q1, pool_k, pool_v, tab, lens,
                                 layer=layer, interpret=True)
        tol = 1e-1 if pool_dtype == jnp.float8_e4m3fn else 2e-5
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=tol, rtol=tol)
        assert np.all(np.asarray(out[2]) == 0.0)        # empty slot


def test_flash_decode_paged_matches_contiguous():
    """Scattering a contiguous cache across out-of-order pages and reading
    it back through the block table reproduces the contiguous kernel."""
    B, KV, G, hd, ps, npg = 2, 2, 2, 16, 4, 4
    H, S = KV * G, ps * 4
    ks = jax.random.split(KEY, 3)
    q1 = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    P1 = B * npg + 1
    perm = np.random.default_rng(3).permutation(B * npg)
    tab = jnp.asarray(perm.reshape(B, npg), jnp.int32)
    pool_k = jnp.zeros((1, P1, KV, ps, hd))
    pool_v = jnp.zeros((1, P1, KV, ps, hd))
    for b in range(B):
        for pi in range(npg):
            blk_k = k[b, :, pi * ps:(pi + 1) * ps]
            blk_v = v[b, :, pi * ps:(pi + 1) * ps]
            pool_k = pool_k.at[0, perm[b * npg + pi]].set(blk_k)
            pool_v = pool_v.at[0, perm[b * npg + pi]].set(blk_v)
    lens = jnp.asarray([S, S - 3], jnp.int32)
    ref = flash_decode(q1, k, v, lens, block_k=ps, interpret=True)
    out = flash_decode_paged(q1, pool_k, pool_v, tab, lens, layer=0,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_backend_registry():
    """set_backend validates eagerly (ValueError, not a strippable
    assert); use_backend scopes and restores the process global."""
    assert kops.check_backend("ref") == "ref"
    with pytest.raises(ValueError, match="unknown kernel backend"):
        kops.set_backend("cuda")
    before = kops.KERNEL_BACKEND
    with kops.use_backend("interpret"):
        assert kops.KERNEL_BACKEND == "interpret"
        with kops.use_backend("ref"):
            assert kops.KERNEL_BACKEND == "ref"
        assert kops.KERNEL_BACKEND == "interpret"
    assert kops.KERNEL_BACKEND == before
    with pytest.raises(ValueError):
        with kops.use_backend("mosaic"):
            pass
    assert kops.KERNEL_BACKEND == before


def test_ops_dispatch_uses_ambient_backend():
    """ops.decode_attention honors use_backend when no explicit backend
    is passed, and both routes agree."""
    B, H, KV, S, hd = 2, 4, 2, 32, 16
    ks = jax.random.split(KEY, 3)
    q1 = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    lens = jnp.asarray([32, 7], jnp.int32)
    ref = kops.decode_attention(q1, k, v, lens)       # default "ref"
    with kops.use_backend("interpret"):
        out = kops.decode_attention(q1, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("B,H,S,hd", [(1, 2, 64, 16), (2, 3, 96, 32),
                                       (1, 2, 50, 16)])   # 50: padded
@pytest.mark.parametrize("chunk", [8, 16])
def test_rwkv6_chunked(B, H, S, hd, chunk):
    ks = jax.random.split(KEY, 5)
    r, k, v = (0.5 * jax.random.normal(ks[i], (B, H, S, hd))
               for i in range(3))
    w = -jnp.exp(jnp.clip(jax.random.normal(ks[3], (B, H, S, hd)),
                          -8.0, 1.386))
    u = 0.3 * jnp.ones((H, hd)) + 0.1 * jax.random.normal(ks[4], (H, hd))
    y_ref, st_ref = kref.rwkv6_ref(r, k, v, w, u)
    y, st = rwkv6_chunked(r, k, v, w, u, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=5e-4,
                               rtol=5e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref), atol=5e-4,
                               rtol=5e-4)


@pytest.mark.parametrize("B,H,S,N,P", [(1, 2, 64, 8, 16), (2, 4, 128, 16, 32),
                                       (1, 2, 100, 8, 16)])  # 100: padded
@pytest.mark.parametrize("chunk", [16, 64])
def test_ssd_chunked(B, H, S, N, P, chunk):
    ks = jax.random.split(KEY, 4)
    x = 0.5 * jax.random.normal(ks[0], (B, H, S, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, S)))
    Bm = 0.5 * jax.random.normal(ks[2], (B, S, N))
    Cm = 0.5 * jax.random.normal(ks[3], (B, S, N))
    a = -jnp.exp(jnp.linspace(0.0, 2.0, H))
    y_ref, h_ref = kref.ssd_ref(x, dt, Bm, Cm, a)
    y, h = ssd_chunked(x, dt, Bm, Cm, a, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("E,C,d,f", [(2, 32, 16, 24), (4, 64, 48, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul(E, C, d, f, dtype):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (E, C, d), dtype)
    w = jax.random.normal(ks[1], (E, d, f), dtype)
    ref = kref.gmm_ref(x, w)
    out = grouped_matmul(x, w, block_c=16, block_f=16, block_d=16,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_model_chunked_paths_match_kernels():
    """The model's jnp chunked rwkv6/ssd (used in the dry-run) agree with the
    sequential oracles — same math as the Pallas kernels."""
    from repro.models import ssm as mssm
    B, H, S, hd = 2, 2, 64, 16
    d = H * hd
    ks = jax.random.split(KEY, 2)
    # rwkv6 chunked-vs-step consistency via the model API
    p = {
        "mu_r": jnp.full((d,), 0.5), "mu_k": jnp.full((d,), 0.5),
        "mu_v": jnp.full((d,), 0.5), "mu_g": jnp.full((d,), 0.5),
        "mu_w": jnp.full((d,), 0.5),
        "wr": 0.1 * jax.random.normal(ks[0], (d, d)),
        "wk": 0.1 * jax.random.normal(ks[1], (d, d)),
        "wv": 0.1 * jax.random.normal(ks[0], (d, d)),
        "wg": 0.1 * jax.random.normal(ks[1], (d, d)),
        "wo": 0.1 * jax.random.normal(ks[0], (d, d)),
        "w0": jnp.full((d,), -2.0),
        "wa": jnp.zeros((d, 64)), "wb": jnp.zeros((64, d)),
        "u": jnp.full((H, hd), 0.3),
        "gn_scale": jnp.ones((d,)), "gn_bias": jnp.zeros((d,)),
    }
    x = 0.5 * jax.random.normal(ks[1], (B, S, d))
    y_chunk, stT, _ = mssm.rwkv6_mix(p, x, heads=H, chunk=16)
    # sequential: one token at a time
    st = jnp.zeros((B, H, hd, hd))
    prev = None
    outs = []
    for t in range(S):
        y_t, st, prev = mssm.rwkv6_mix_step(p, x[:, t:t + 1], st, prev,
                                            heads=H)
        outs.append(y_t)
    y_seq = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_seq),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(stT), np.asarray(st), atol=2e-4,
                               rtol=2e-4)
