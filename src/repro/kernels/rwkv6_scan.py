"""Pallas TPU chunked WKV6 recurrence (RWKV6 'Finch', per-channel decay).

Grid (b, h, chunk) with the chunk axis innermost; the [hd, hd] f32 state
persists in VMEM scratch across chunks (reset at chunk 0). Within a chunk the
per-channel decay factorizes into row/col scalings of the score matrix
(r'_t = r_t * exp(cs_{t-1}), k'_s = k_s * exp(-cs_s)), turning the recurrence
into two MXU matmuls + a strictly-lower-triangular mask. Chunk size is capped
at 16 so exp(-cs) stays within f32 range under the model's clamped log-decay
(|logw| <= 4 per step; see repro.models.ssm._LOGW_CLIP and DESIGN.md). The
in-chunk cumulative sum is a lower-triangular matmul at HIGHEST precision
(Mosaic has no cumsum). A sequence that does not divide the chunk is padded
with decay-free, key-free positions (log-decay 0, k = 0) that leave the
state unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, st_ref, state_sc,
                 *, C, hd, n_chunks):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _reset():
        state_sc[...] = jnp.zeros_like(state_sc)

    r = r_ref[0, 0].astype(jnp.float32)              # [C, hd]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)              # log-decay <= 0
    u = u_ref[0].astype(jnp.float32)                 # [1, hd]

    ti = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    cs = jax.lax.dot((ti >= si).astype(jnp.float32), w,
                     precision=jax.lax.Precision.HIGHEST)   # cumsum [C, hd]
    cs_prev = cs - w
    r_p = r * jnp.exp(cs_prev)
    k_p = k * jnp.exp(-cs)

    scores = jax.lax.dot_general(r_p, k_p, (((1,), (1,)), ((), ())))
    scores = jnp.where(ti > si, scores, 0.0)         # strict lower
    y = jax.lax.dot(scores, v)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)  # u-bonus on t == s
    y += diag * v
    y += jax.lax.dot(r_p, state_sc[...])             # inter-chunk

    decay = jnp.exp(cs[C - 1:C, :]).T                # [hd, 1]
    state_sc[...] = decay * (
        state_sc[...] + jax.lax.dot_general(k_p, v, (((0,), (0,)), ((), ()))))
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _write_state():
        st_ref[0, 0] = state_sc[...]


def rwkv6_chunked(r, k, v, w, u, *, chunk=16, interpret=False):
    """r,k,v,w [B,H,S,hd] (w = log-decay <= 0); u [H,hd].
    Returns (y [B,H,S,hd], final_state [B,H,hd,hd] f32)."""
    B, H, S, hd = r.shape
    C = min(chunk, S)
    n = -(-S // C)
    if n * C != S:
        pad = ((0, 0), (0, 0), (0, n * C - S), (0, 0))
        r, k, v, w = (jnp.pad(a, pad) for a in (r, k, v, w))
    u = u.reshape(H, 1, hd)
    kernel = functools.partial(_wkv6_kernel, C=C, hd=hd, n_chunks=n)
    y, st = pl.pallas_call(
        kernel,
        grid=(B, H, n),
        in_specs=[
            pl.BlockSpec((1, 1, C, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, h, c: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, n * C, hd), r.dtype),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u)
    return y[:, :, :S], st
