"""Pallas TPU chunked SSD (Mamba-2 style selective state space).

Grid (b, h, chunk), chunk innermost; the [N, P] f32 state persists in VMEM
scratch. Scalar-per-head decay makes the intra-chunk decay matrix
L[t,s] = exp(cs_t - cs_s) numerically safe (always <= 1) at any chunk size;
chunk 64 keeps tiles MXU-friendly while the state tile (N x P = 16 x 64) is
VPU-resident. dt rides as a [.., S, 1] column (so its block is legal at any
head count), the per-head decay `a` sits in SMEM, and the in-chunk cumulative
sum is a lower-triangular matmul at HIGHEST precision (Mosaic has no cumsum).
A sequence that does not divide the chunk is padded with dt = 0 positions,
which neither decay nor feed the state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, st_ref, state_sc,
                *, C, n_chunks):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _reset():
        state_sc[...] = jnp.zeros_like(state_sc)

    x = x_ref[0, 0].astype(jnp.float32)              # [C, P]
    dt = dt_ref[0, 0].astype(jnp.float32)            # [C, 1]
    Bm = b_ref[0].astype(jnp.float32)                # [C, N]
    Cm = c_ref[0].astype(jnp.float32)
    a = a_ref[pl.program_id(1)]                      # scalar < 0

    ti = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    causal = ti >= si
    la = dt * a                                      # [C, 1] log-decay
    cs = jax.lax.dot(causal.astype(jnp.float32), la,
                     precision=jax.lax.Precision.HIGHEST)   # cumsum [C, 1]
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))   # [C, C]
    L = jnp.where(causal, jnp.exp(cs - cs.T), 0.0)
    y = jax.lax.dot(cb * L * dt.T, x)                # intra-chunk
    y += jax.lax.dot(Cm * jnp.exp(cs), state_sc[...])  # inter
    last = jnp.sum(la)                               # chunk's total decay
    dec = jnp.exp(last - cs) * dt                    # [C, 1]
    state_sc[...] = jnp.exp(last) * state_sc[...] + jax.lax.dot_general(
        Bm * dec, x, (((0,), (0,)), ((), ())))
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _write_state():
        st_ref[0, 0] = state_sc[...]


def ssd_chunked(x, dt, B_, C_, a, *, chunk=64, interpret=False):
    """x [B,H,S,P]; dt [B,H,S]; B_/C_ [B,S,N]; a [H] < 0.
    Returns (y [B,H,S,P], final_state [B,H,N,P] f32)."""
    B, H, S, Pd = x.shape
    N = B_.shape[-1]
    C = min(chunk, S)
    n = -(-S // C)
    if n * C != S:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, n * C - S), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, n * C - S)))
        B_, C_ = (jnp.pad(m, ((0, 0), (0, n * C - S), (0, 0)))
                  for m in (B_, C_))
    dt = dt[..., None]
    kernel = functools.partial(_ssd_kernel, C=C, n_chunks=n)
    y, st = pl.pallas_call(
        kernel,
        grid=(B, H, n),
        in_specs=[
            pl.BlockSpec((1, 1, C, Pd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, C, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, C, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C, Pd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, N, Pd), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, n * C, Pd), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, Pd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, Pd), jnp.float32)],
        interpret=interpret,
    )(x, dt, B_, C_, jnp.asarray(a, jnp.float32))
    return y[:, :, :S], st
