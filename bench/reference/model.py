"""Plain reference of the two configurations' decoder (Qwen3 and
H2O-Danube/Mistral-style): straightforward jax.numpy over the whole
sequence, no cache, no kernels, no batching tricks.

It follows the published description (pre-norm RMSNorm blocks, rotary
position embedding on the two halves of each head, grouped-query attention
where query head h reads key/value head h // (H / KV), optional RMSNorm of
each query and key head before the rotation (Qwen3), a causal mask that a
sliding window narrows (Mistral: keys with i - j < window), SwiGLU MLP,
final RMSNorm, tied or separate output head). Its sizes come from the
benchmark's configuration file, never from the program.

The weights it reads are the benchmark's own (bench/common.py make_params),
laid out as the program's parameter tree; `layer()` says how each leaf is
read: a norm leaf holds the offset from a gain of 1, `mlp_wi` stacks the
gate and up projections on its third axis.

`dtype` is the precision of every matrix product's operands.
`dtype=float32` runs every matrix product at HIGHEST precision (the TPU's
default would round f32 operands to one bf16 pass). `dtype=bfloat16` holds
weights and activations in bf16, norms and softmax in f32 and cast back,
as bf16 checkpoints are served. `dtype=CONTROL` (fp8 e4m3) is the control:
the programs run f32 at the TPU's default precision, whose products already
take one bf16 pass, so bf16 reads like the program (PERF.md) and the
control is the step below it. Each operand is scaled per tensor to fp8's
range and rounded to fp8, as fp8 serving quantizes; products, the residual
stream, norms and softmax stay in f32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG = -1e30
CONTROL = jnp.float8_e4m3fn


def compute_dtype(dt):
    """What a precision's values are held and added in: f32 below 16 bits."""
    return jnp.float32 if jnp.dtype(dt).itemsize < 2 else dt


def cast(a, dt):
    """`a` as an operand of precision dt, held in compute_dtype(dt). Below
    16 bits the rounding passes gradients straight through, in f32: fp8
    cotangents would underflow to zero, which no fp8 training does."""
    if jnp.dtype(dt).itemsize >= 2:
        return a.astype(dt)
    a = a.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / float(jnp.finfo(dt).max)
    r = (a / scale).astype(dt).astype(jnp.float32) * scale
    return a + jax.lax.stop_gradient(r - a)


def dims(cfg: dict) -> dict:
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    return dict(d=cfg["hidden_size"], H=H, KV=KV, hd=hd,
                L=cfg["num_hidden_layers"], V=cfg["vocab_size"],
                eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"],
                window=cfg.get("sliding_window") or 0,
                qk_norm=bool(cfg.get("qk_norm")),
                tied=bool(cfg["tie_word_embeddings"]))


def rms_norm(x, gain_offset, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + gain_offset.astype(jnp.float32))).astype(dt)


def rope(x, theta):
    """x [T, heads, hd]; positions 0..T-1; rotate the two halves."""
    T, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)


def layer(m: dict, p: dict, x, dt):
    """One decoder layer over x [T, d]; p holds this layer's leaves."""
    T = x.shape[0]
    H, KV, hd = m["H"], m["KV"], m["hd"]
    h = cast(rms_norm(x, p["ln1"], m["eps"]), dt)
    q = (h @ cast(p["wq"], dt)).reshape(T, H, hd)
    k = (h @ cast(p["wk"], dt)).reshape(T, KV, hd)
    v = (h @ cast(p["wv"], dt)).reshape(T, KV, hd)
    if m["qk_norm"]:
        q = rms_norm(q, p["q_norm"], m["eps"])
        k = rms_norm(k, p["k_norm"], m["eps"])
    q, k = rope(q, m["theta"]), rope(k, m["theta"])
    k = jnp.repeat(k, H // KV, axis=1)           # head h reads kv h // (H/KV)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", cast(q, dt), cast(k, dt),
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    mask = j <= i
    if m["window"]:
        mask &= (i - j) < m["window"]
    s = jnp.where(mask[None], s, NEG)
    a = cast(jax.nn.softmax(s, axis=-1), dt)
    o = jnp.einsum("hqk,khd->qhd", a, cast(v, dt)).reshape(T, H * hd)
    x = x + cast(o, dt) @ cast(p["wo"], dt)
    h = cast(rms_norm(x, p["ln2"], m["eps"]), dt)
    wi = cast(p["mlp_wi"], dt)
    g, u = h @ wi[:, 0, :], h @ wi[:, 1, :]
    return x + cast(jax.nn.silu(g) * u, dt) @ cast(p["mlp_wo"], dt)


def hidden(cfg: dict, params, tokens, dtype=jnp.float32):
    """Final-normed hidden states [T, d] of one sequence of token ids."""
    m = dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = cast(params["embed"][tokens], dtype)

        def body(x, p):
            return layer(m, p, x, dtype), None

        if jnp.dtype(dtype).itemsize < 2:
            # the control's f32 copies of its rounded weights would be
            # kept for every layer's backward pass: recompute them instead
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["blocks"])
        return rms_norm(x, params["final_norm"], m["eps"])


def head(cfg: dict, params, dtype=jnp.float32):
    """[d, V] output projection (the embedding, transposed, when tied)."""
    m = dims(cfg)
    w = params["embed"].T if m["tied"] else params["head"]
    return cast(w[:, :m["V"]], dtype)


def logits(cfg: dict, params, h, dtype=jnp.float32):
    with jax.default_matmul_precision("highest"):
        return (cast(h, dtype) @ head(cfg, params, dtype)).astype(jnp.float32)


def loss(cfg: dict, params, tokens, labels, dtype=jnp.float32):
    """Mean next-token cross entropy of one batch [B, S] (rows one by one,
    so that only one row's activations are live)."""
    def one(tot, xy):
        x, y = xy
        lg = logits(cfg, params, hidden(cfg, params, x, dtype), dtype)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
        return tot + jnp.sum(lse - gold), None

    tot, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros((), jnp.float32),
                          (tokens, labels))
    return tot / tokens.size
