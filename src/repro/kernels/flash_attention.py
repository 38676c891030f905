"""Pallas TPU flash attention (forward), GQA + causal + sliding window.

Grid (b, kv_head, q_block, k_block) with the k_block axis innermost: TPU grids
execute sequentially per core, so the online-softmax accumulators (m, l, acc)
live in VMEM scratch across k_block steps and the output tile is written once
at the last k block. Causal / windowed tiles outside the band are skipped with
pl.when (zero compute on TPU, unlike the masked jnp path — this is the kernel's
FLOPs win over the XLA fallback).

BlockSpecs keep one (bq x hd) q tile, one (bk x hd) k/v tile, and the f32
accumulators resident in VMEM: bq=bk=128, hd<=256 => ~0.5 MB << 16 MB VMEM,
with MXU-aligned (128) matmul dims.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                 scale, causal, window, bq, bk, nk, kv_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_start = qi * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _reset():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # band check: does this (q,k) tile intersect the causal/window band?
    # (k tiles made only of padding past kv_len are dead too)
    live = k_start < kv_len
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + bq - 1)
    if window > 0:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        gq = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        gk = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = gk < kv_len
        if causal:
            mask &= gq >= gk
        if window > 0:
            mask &= (gq - gk) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot(p, v)
        m_sc[...] = m_new

    @pl.when(ki == nk - 1)
    def _write():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


def _pad_to(x, n):
    """Pad x's sequence axis (2) with zeros up to length n."""
    pad = n - x.shape[2]
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x


def flash_attention_fwd(q, k, v, *, causal=True, window=0,
                        block_q=128, block_k=128, interpret=False):
    """q [B,H,Sq,hd]; k,v [B,KV,Sk,hd] -> [B,H,Sq,hd].

    A sequence shorter than its block is one whole-axis block; a longer
    one that does not divide it is padded up to a block multiple (dead
    keys past Sk are masked, padded query rows are sliced off) rather
    than shrinking the block to an unaligned size Mosaic refuses."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    q, k, v = _pad_to(q, nq * bq), _pad_to(k, nk * bk), _pad_to(v, nk * bk)

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _attn_kernel, scale=hd ** -0.5, causal=causal, window=window,
        bq=bq, bk=bk, nk=nk, kv_len=Sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
