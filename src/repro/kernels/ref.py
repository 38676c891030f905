"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Layouts (kernel-native):
  attention: q [B, H, Sq, hd]; k, v [B, KV, Sk, hd]  (GQA: G = H // KV)
  rwkv6:     r,k,v,w [B, H, S, hd] (w = log-decay <= 0); u [H, hd]
  ssd:       x [B, H, S, P]; dt [B, H, S]; B_,C_ [B, S, N]; a [H] < 0
  gmm:       x [E, C, d]; w [E, d, f]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(q, k, v, *, causal=True, window=0):
    B, H, Sq, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, KV, G, Sq, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bkgqd,bkcd->bkgqc", qf, kf) * hd ** -0.5
    Sk = k.shape[2]
    gq = jnp.arange(Sq)[:, None] + (Sk - Sq)      # align ends (decode tail)
    gk = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= gq >= gk
    if window > 0:
        mask &= (gq - gk) < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqc,bkcd->bkgqd", p, vf)
    return o.reshape(B, H, Sq, hd).astype(q.dtype)


def decode_ref(q1, k, v, length, *, window=0):
    """q1 [B, H, hd]; k/v [B, KV, S, hd]; attend to positions < length.

    `length` is a scalar or a per-row [B] vector. Fully-masked rows
    (length == 0) return zeros — the same contract as the Pallas kernel's
    `l = max(l, 1e-30)` guard (a plain softmax would degenerate to a
    uniform average over uninitialized V rows)."""
    B, H, hd = q1.shape
    KV, S = k.shape[1], k.shape[2]
    G = H // KV
    qf = q1.astype(jnp.float32).reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,bkcd->bkgc", qf, k.astype(jnp.float32)) * hd ** -0.5
    pos = jnp.arange(S)[None, None, None, :]
    lens = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1),
                            (B,))[:, None, None, None]
    valid = pos < lens
    if window > 0:
        valid &= pos >= (lens - window)
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bkgc,bkcd->bkgd", p / l, v.astype(jnp.float32))
    return o.reshape(B, H, hd).astype(q1.dtype)


def decode_paged_ref(q1, k_pool, v_pool, block_tab, lengths, *, layer=0):
    """Oracle for flash_decode_paged: gather the paged pool into a
    contiguous per-row view (exactly the materialization the fused kernel
    avoids), then run decode_ref with per-row lengths.

    q1 [B,H,hd]; pools [groups, num_pages+1, KV, page_size, hd] (last
    page = trash); block_tab [B, pages_per_slot] int32 (-1 = unmapped ->
    trash); lengths scalar or [B]."""
    B = q1.shape[0]
    groups, P1, KV, ps, hd = k_pool.shape
    phys = jnp.where(block_tab >= 0, block_tab, P1 - 1)     # [B, npg]

    def view(pool):
        pages = pool[layer][phys]                           # [B,npg,KV,ps,hd]
        return pages.transpose(0, 2, 1, 3, 4).reshape(B, KV, -1, hd)

    return decode_ref(q1, view(k_pool), view(v_pool), lengths, window=0)


def rwkv6_ref(r, k, v, w, u, state0=None):
    """Sequential WKV6 recurrence. Returns (y [B,H,S,hd], final_state)."""
    B, H, S, hd = r.shape
    rf, kf, vf, wf = (a.astype(jnp.float32) for a in (r, k, v, w))
    uf = u.astype(jnp.float32)
    if state0 is None:
        state0 = jnp.zeros((B, H, hd, hd), jnp.float32)

    def step(st, xs):
        r_, k_, v_, w_ = xs                      # [B,H,hd]
        kv = jnp.einsum("bhi,bhj->bhij", k_, v_)
        y = jnp.einsum("bhi,bhij->bhj", r_, st + uf[None, :, :, None] * kv)
        st = jnp.exp(w_)[..., None] * st + kv
        return st, y

    xs = tuple(a.transpose(2, 0, 1, 3) for a in (rf, kf, vf, wf))
    stT, ys = jax.lax.scan(step, state0, xs)
    return ys.transpose(1, 2, 0, 3).astype(r.dtype), stT


def ssd_ref(x, dt, B_, C_, a, state0=None):
    """Sequential SSD. x [B,H,S,P], dt [B,H,S], B_/C_ [B,S,N], a [H]<0.
    Returns (y [B,H,S,P], final_state [B,H,N,P])."""
    B, H, S, Pd = x.shape
    N = B_.shape[-1]
    if state0 is None:
        state0 = jnp.zeros((B, H, N, Pd), jnp.float32)

    def step(h, xs):
        x_, dt_, b_, c_ = xs                     # [B,H,P],[B,H],[B,N],[B,N]
        dec = jnp.exp(dt_ * a[None, :])
        h = dec[..., None, None] * h + jnp.einsum(
            "bn,bh,bhp->bhnp", b_, dt_, x_)
        y = jnp.einsum("bn,bhnp->bhp", c_, h)
        return h, y

    xs = (x.transpose(2, 0, 1, 3).astype(jnp.float32),
          dt.transpose(2, 0, 1).astype(jnp.float32),
          B_.transpose(1, 0, 2).astype(jnp.float32),
          C_.transpose(1, 0, 2).astype(jnp.float32))
    hT, ys = jax.lax.scan(step, state0, xs)
    return ys.transpose(1, 2, 0, 3).astype(x.dtype), hT


def gmm_ref(x, w):
    """Grouped (expert-batched) matmul: [E,C,d] x [E,d,f] -> [E,C,f]."""
    return jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)
