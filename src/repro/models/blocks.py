"""Per-layer block application for every assigned architecture family.

A "layer" is applied with a uniform signature so pipeline stages can unroll
their layer slots under SPMD (all stages execute the same program; per-layer
behaviour — attention kind, cache group slot — is data, not structure).

Conventions:
  x [B, S, d]; params p are the per-layer leaves (no layer dim, local tp shard)
  kind: 0 = full attention, 1 = windowed, (ssm archs: ignored)
  cache: dict of stage-local cache groups (see lm.init_cache)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops as kernel_ops
from repro.models import attention as attn_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import rms_norm, layer_norm, rope_cos_sin, apply_rope
from repro.serve import cache as cache_lib


@dataclass
class LayerCtx:
    mode: str                       # train | prefill | decode
    pos: Any = None                 # decode position (traced scalar)
    q_offset: int = 0
    tp_axis: Optional[str] = None   # mesh axis for TP reductions
    merge_axis: Optional[str] = None  # seq-sharded KV merge axis (long decode)
    seq_offset: Any = 0             # this shard's first cache slot position
    kind: Any = 0                   # 0 full / 1 windowed (python or traced int)
    full_i: Any = 0                 # slot in the stage-local full-KV group
    win_i: Any = 0                  # slot in the stage-local windowed group
    ssm_i: Any = 0                  # slot in the stage-local ssm group
    valid: Any = True               # padded layer slots are masked out
    lens: Any = None                # per-row prompt lengths ([B]) — prefill
                                    # of variable-length (right-padded)
                                    # prompts; None = every row is full
    kernel_backend: str = "ref"     # "ref" = jnp paths; "interpret"/"tpu"
                                    # route the full-attention prefill/decode
                                    # and chunked SSM mixes through the
                                    # repro.kernels Pallas kernels


def _psum(x, axis):
    return jax.lax.psum(x, axis) if axis is not None else x


def _norm(cfg: ArchConfig, p, key, x):
    if cfg.norm_style == "ln_pre":
        return layer_norm(x, p[key], p[key + "_b"], eps=cfg.norm_eps)
    return rms_norm(x, p[key], eps=cfg.norm_eps)


def _mlp_dense(cfg: ArchConfig, p, x):
    """wi [d, G, ffl], wo [ffl, d]. Returns the pre-psum partial."""
    h = jnp.einsum("bsd,dgf->bsgf", x, p["mlp_wi"].astype(x.dtype))
    if cfg.mlp_type == "swiglu":
        a = jax.nn.silu(h[..., 0, :]) * h[..., 1, :]
    elif cfg.mlp_type == "geglu":
        a = jax.nn.gelu(h[..., 0, :], approximate=True) * h[..., 1, :]
    elif cfg.mlp_type == "gelu":
        a = jax.nn.gelu(h[..., 0, :], approximate=True)
    else:  # relu2
        r = jax.nn.relu(h[..., 0, :])
        a = r * r
    return a @ p["mlp_wo"].astype(x.dtype)


def _mlp_moe(cfg: ArchConfig, p, x, tp_axis):
    from repro.models.moe import moe_mlp
    moe_p = {"router": p["router"], "w_in": p["moe_w_in"],
             "w_out": p["moe_w_out"]}
    out, aux = moe_mlp(
        moe_p, x, num_experts=cfg.num_experts, top_k=cfg.top_k,
        mlp_type=cfg.mlp_type, capacity_factor=cfg.capacity_factor)
    return _psum(out, tp_axis), aux


# ----------------------------------------------------------------------------
# Attention mix (dense / moe / hybrid attention branch)
# ----------------------------------------------------------------------------
def _qkv(cfg: ArchConfig, p, xn, ctx: LayerCtx):
    B, S, _ = xn.shape
    hd = cfg.head_dim
    q = (xn @ p["wq"].astype(xn.dtype)).reshape(B, S, -1, hd)
    k = (xn @ p["wk"].astype(xn.dtype)).reshape(B, S, -1, hd)
    v = (xn @ p["wv"].astype(xn.dtype)).reshape(B, S, -1, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], eps=cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], eps=cfg.norm_eps)
    # rope (theta may differ for global layers, e.g. gemma3)
    tl = cfg.rope_theta
    tg = cfg.rope_theta_global or cfg.rope_theta
    if isinstance(ctx.kind, int):
        theta = tg if ctx.kind == 0 else tl
    else:
        theta = jnp.where(ctx.kind == 0, tg, tl)
    if ctx.mode == "decode":
        p_ = jnp.asarray(ctx.pos)
        # scalar pos -> [1] (broadcast over batch); per-row pos [B] -> [B, 1]
        positions = p_[None] if p_.ndim == 0 else p_[:, None]
    else:
        positions = ctx.q_offset + jnp.arange(S)
    cos, sin = rope_cos_sin(positions, hd, theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def _attn_train(cfg: ArchConfig, p, xn, ctx: LayerCtx, cache):
    """Train/prefill attention; writes cache in prefill. Pre-psum partial out."""
    q, k, v = _qkv(cfg, p, xn, ctx)
    B, S, Hl, hd = q.shape

    def full_path():
        if ctx.kernel_backend != "ref":
            return kernel_ops.attention(q, k, v, causal=True, window=0,
                                        backend=ctx.kernel_backend)
        return attn_lib.flash_attention(q, k, v, causal=True, window=0)

    def win_path():
        return attn_lib.banded_attention(q, k, v, window=cfg.window_size)

    if isinstance(ctx.kind, int):
        o = full_path() if ctx.kind == 0 else win_path()
    else:
        o = jax.lax.cond(ctx.kind == 0, full_path, win_path)

    new_cache = cache
    if ctx.mode == "prefill" and cache is not None:
        new_cache = dict(cache)
        if "kv_full" in cache:
            kf, vf = cache["kv_full"]
            i = jnp.asarray(ctx.full_i)
            sel = jnp.asarray(ctx.kind == 0)
            if cache_lib.is_paged(cache):
                tab = cache["block_tab"]
                sel_b = jnp.broadcast_to(sel & jnp.asarray(ctx.valid),
                                         (B,))
                kf = cache_lib.page_write_prompt(kf, i, tab, k, sel_b,
                                                 ctx.lens)
                vf = cache_lib.page_write_prompt(vf, i, tab, v, sel_b,
                                                 ctx.lens)
            else:
                Sc = kf.shape[2]
                ks = k[:, -Sc:] if S >= Sc else jnp.pad(k, ((0, 0), (0, Sc - S), (0, 0), (0, 0)))
                vs = v[:, -Sc:] if S >= Sc else jnp.pad(v, ((0, 0), (0, Sc - S), (0, 0), (0, 0)))
                kf = kf.at[i].set(jnp.where(sel, ks.astype(kf.dtype), kf[i]))
                vf = vf.at[i].set(jnp.where(sel, vs.astype(vf.dtype), vf[i]))
            new_cache["kv_full"] = (kf, vf)
        if "kv_win" in cache:
            kw, vw = cache["kv_win"]
            W = kw.shape[2]
            # ring layout: slot = position % W
            sel = jnp.asarray(ctx.kind == 1)
            i = jnp.asarray(ctx.win_i)
            if ctx.lens is None:
                take = min(W, S)
                kl, vl = k[:, -take:], v[:, -take:]
                pos_tail = ctx.q_offset + S - take + jnp.arange(take)
                slots = pos_tail % W
                kw_i = kw[i].at[:, slots].set(kl.astype(kw.dtype))
                vw_i = vw[i].at[:, slots].set(vl.astype(vw.dtype))
            else:
                # variable-length rows: walk the prompt in W-sized chunks so
                # each write's ring slots are unique; positions >= lens[b]
                # keep the slot's previous value, so every row's ring ends
                # up holding exactly its own last min(W, lens[b]) tokens
                kw_i, vw_i = kw[i], vw[i]
                for c0 in range(0, S, W):
                    take = min(W, S - c0)
                    gpos = ctx.q_offset + c0 + jnp.arange(take)
                    slots = gpos % W
                    live = gpos[None, :] < ctx.lens[:, None]   # [B, take]
                    k_c = jnp.where(live[..., None, None],
                                    k[:, c0:c0 + take].astype(kw.dtype),
                                    kw_i[:, slots])
                    v_c = jnp.where(live[..., None, None],
                                    v[:, c0:c0 + take].astype(vw.dtype),
                                    vw_i[:, slots])
                    kw_i = kw_i.at[:, slots].set(k_c)
                    vw_i = vw_i.at[:, slots].set(v_c)
            kw = kw.at[i].set(jnp.where(sel, kw_i, kw[i]))
            vw = vw.at[i].set(jnp.where(sel, vw_i, vw[i]))
            new_cache["kv_win"] = (kw, vw)
    return o.reshape(B, S, Hl * hd) @ p["wo"].astype(xn.dtype), new_cache


def _attn_decode(cfg: ArchConfig, p, xn, ctx: LayerCtx, cache):
    """Single-token attention against the stage-local cache groups. ctx.pos
    is a scalar (aligned batch) or a [B] vector (continuous batching: each
    row at its own depth). Full-attention K/V is read through the block
    table (paged trees) or directly (the contiguous reference layout)."""
    q, k, v = _qkv(cfg, p, xn, ctx)
    B, _, Hl, hd = q.shape
    pos_a = jnp.asarray(ctx.pos)
    per_row = pos_a.ndim == 1
    new_cache = dict(cache)
    outs = []

    if "kv_full" in cache and cache_lib.is_paged(cache):
        kf, vf = cache["kv_full"]
        i = jnp.asarray(ctx.full_i)
        tab = cache["block_tab"]
        cap = tab.shape[1] * kf.shape[3]                # pps * page_size
        pos_b = jnp.broadcast_to(pos_a, (B,))
        sel = jnp.asarray(ctx.kind == 0) & jnp.asarray(ctx.valid)
        sel_b = jnp.broadcast_to(sel, (B,)) & (pos_b >= 0) & (pos_b < cap)
        kf = cache_lib.page_write_token(kf, i, tab, pos_b, k, sel_b)
        vf = cache_lib.page_write_token(vf, i, tab, pos_b, v, sel_b)
        new_cache["kv_full"] = (kf, vf)
        if ctx.kernel_backend != "ref":
            # fused walk: the kernel indexes the pool through the block
            # table with per-row lengths — no page_view materialization
            lens_row = jnp.clip(pos_b + 1, 0, cap)
            o_full = kernel_ops.decode_attention_paged(
                q[:, 0], kf, vf, tab, lens_row, layer=i,
                backend=ctx.kernel_backend)[:, None]
        else:
            k_view, gpos = cache_lib.page_view(kf, i, tab)
            v_view, _ = cache_lib.page_view(vf, i, tab)
            o_full = attn_lib.decode_attend(q, k_view, v_view, gpos, ctx.pos,
                                            window=0, merge_axis=None)
        outs.append((0, o_full))
    elif "kv_full" in cache:
        kf, vf = cache["kv_full"]
        i = jnp.asarray(ctx.full_i)
        Sc = kf.shape[2]
        li = pos_a - ctx.seq_offset                     # scalar or [B]
        in_rng = (li >= 0) & (li < Sc)
        lic = jnp.clip(li, 0, Sc - 1)
        sel = jnp.asarray(ctx.kind == 0) & in_rng & jnp.asarray(ctx.valid)
        if per_row:
            kf = cache_lib.upd_kv_rows(kf, i, lic, k, sel)
            vf = cache_lib.upd_kv_rows(vf, i, lic, v, sel)
        else:
            kf = cache_lib.upd_kv(kf, i, lic, k, sel)
            vf = cache_lib.upd_kv(vf, i, lic, v, sel)
        new_cache["kv_full"] = (kf, vf)
        if ctx.kernel_backend != "ref" and ctx.merge_axis is None:
            # per-row live lengths; rows outside this shard's range clip
            # to an empty (zero-output) window, matching the sel mask
            lens_row = jnp.clip(jnp.broadcast_to(pos_a, (B,)) + 1
                                - ctx.seq_offset, 0, Sc)
            o_full = kernel_ops.decode_attention(
                q[:, 0], kf[i].transpose(0, 2, 1, 3),
                vf[i].transpose(0, 2, 1, 3), lens_row, window=0,
                backend=ctx.kernel_backend)[:, None]
        else:
            gpos = ctx.seq_offset + jnp.arange(Sc)
            o_full = attn_lib.decode_attend(q, kf[i], vf[i], gpos, ctx.pos,
                                            window=0,
                                            merge_axis=ctx.merge_axis)
        outs.append((0, o_full))

    if "kv_win" in cache:
        kw, vw = cache["kv_win"]
        i = jnp.asarray(ctx.win_i)
        W = kw.shape[2]
        slot = pos_a % W                                # scalar or [B]
        sel = jnp.asarray(ctx.kind == 1) & jnp.asarray(ctx.valid)
        if per_row:
            kw = cache_lib.upd_kv_rows(kw, i, slot, k,
                                       jnp.broadcast_to(sel, (B,)))
            vw = cache_lib.upd_kv_rows(vw, i, slot, v,
                                       jnp.broadcast_to(sel, (B,)))
            # ring slot j holds position pos_b - ((pos_b - j) % W), per row
            gpos = pos_a[:, None] - ((pos_a[:, None] - jnp.arange(W)) % W)
        else:
            kw = cache_lib.upd_kv(kw, i, slot, k, sel)
            vw = cache_lib.upd_kv(vw, i, slot, v, sel)
            gpos = ctx.pos - ((ctx.pos - jnp.arange(W)) % W)
        new_cache["kv_win"] = (kw, vw)
        o_win = attn_lib.decode_attend(q, kw[i], vw[i], gpos, ctx.pos,
                                       window=W + 1, merge_axis=None)
        outs.append((1, o_win))

    if len(outs) == 1:
        o = outs[0][1]
    else:
        o = jnp.where(jnp.asarray(ctx.kind == 0), outs[0][1], outs[1][1])
    return o.reshape(B, 1, Hl * hd) @ p["wo"].astype(xn.dtype), new_cache


# ----------------------------------------------------------------------------
# SSM branches
# ----------------------------------------------------------------------------
def _ssd_branch(cfg: ArchConfig, p, xn, ctx: LayerCtx, cache):
    H, N, di = cfg.n_ssm_heads, cfg.ssm_state, cfg.d_inner
    new_cache = dict(cache) if cache is not None else None
    if ctx.mode == "decode":
        i = jnp.asarray(ctx.ssm_i)
        st, tail = cache["ssm_state"][i], cache["conv_tail"][i]
        y, st2, tail2 = ssm_lib.ssd_mix_step(
            p, xn, st, tail, heads=H, d_state=N, d_inner=di)
        sel = jnp.asarray(ctx.valid)
        new_cache["ssm_state"] = cache["ssm_state"].at[i].set(
            jnp.where(sel, st2, st))
        new_cache["conv_tail"] = cache["conv_tail"].at[i].set(
            jnp.where(sel, tail2.astype(cache["conv_tail"].dtype), tail))
        return y, new_cache
    y, stT, tail = ssm_lib.ssd_mix(p, xn, heads=H, d_state=N, d_inner=di,
                                   lens=ctx.lens if ctx.mode == "prefill"
                                   else None,
                                   kernel_backend=ctx.kernel_backend)
    if ctx.mode == "prefill" and cache is not None:
        i = jnp.asarray(ctx.ssm_i)
        sel = jnp.asarray(ctx.valid)
        new_cache["ssm_state"] = cache["ssm_state"].at[i].set(
            jnp.where(sel, stT, cache["ssm_state"][i]))
        new_cache["conv_tail"] = cache["conv_tail"].at[i].set(
            jnp.where(sel, tail.astype(cache["conv_tail"].dtype),
                      cache["conv_tail"][i]))
    return y, new_cache


def _rwkv_layer(cfg: ArchConfig, p, x, ctx: LayerCtx, cache):
    """Full RWKV6 layer: ln1 + time-mix, ln2 + channel-mix."""
    H = cfg.n_ssm_heads
    xx1 = rms_norm(x, p["ln1"], eps=cfg.norm_eps)
    new_cache = dict(cache) if cache is not None else None
    if ctx.mode == "decode":
        i = jnp.asarray(ctx.ssm_i)
        st = cache["ssm_state"][i]
        shifts = cache["shift"][i]                       # [B, 2, d]
        y, st2, last1 = ssm_lib.rwkv6_mix_step(
            p, xx1, st, shifts[:, 0:1], heads=H)
    else:
        y, st2, last1 = ssm_lib.rwkv6_mix(
            p, xx1, heads=H,
            lens=ctx.lens if ctx.mode == "prefill" else None,
            kernel_backend=ctx.kernel_backend)
    x = x + y
    xx2 = rms_norm(x, p["ln2"], eps=cfg.norm_eps)
    if ctx.mode == "decode":
        prev2 = shifts[:, 1:2]
    else:
        prev2 = None
    xp = ssm_lib._shift(xx2, prev2)
    mk = xx2 + p["cm_mu_k"] * (xp - xx2)
    mr = xx2 + p["cm_mu_r"] * (xp - xx2)
    kk = jax.nn.relu(mk @ p["cm_k"].astype(x.dtype))
    cm = (kk * kk) @ p["cm_v"].astype(x.dtype)
    x = x + jax.nn.sigmoid(mr @ p["cm_r"].astype(x.dtype)) * cm
    if cache is not None:
        i = jnp.asarray(ctx.ssm_i)
        sel = jnp.asarray(ctx.valid)
        if ctx.mode == "prefill" and ctx.lens is not None:
            # channel-mix shift state: the last *real* token per row
            last2 = jnp.take_along_axis(
                xx2, jnp.maximum(ctx.lens - 1, 0)[:, None, None], axis=1)
        else:
            last2 = xx2[:, -1:]
        new_shift = jnp.concatenate([last1, last2], axis=1)
        new_cache["ssm_state"] = cache["ssm_state"].at[i].set(
            jnp.where(sel, st2, cache["ssm_state"][i]))
        new_cache["shift"] = cache["shift"].at[i].set(
            jnp.where(sel, new_shift.astype(cache["shift"].dtype),
                      cache["shift"][i]))
    return x, new_cache


# ----------------------------------------------------------------------------
# Unified layer entry
# ----------------------------------------------------------------------------
def apply_layer(cfg: ArchConfig, p, x, ctx: LayerCtx, cache=None):
    """Returns (x_out, new_cache, aux_loss). Padded slots: x passes through."""
    aux = jnp.zeros((), jnp.float32)
    x_in = x

    if cfg.ssm_type == "rwkv6":
        x, cache = _rwkv_layer(cfg, p, x, ctx, cache)
    else:
        xn = _norm(cfg, p, "ln1", x)
        if ctx.mode == "decode":
            att, cache = _attn_decode(cfg, p, xn, ctx, cache)
        else:
            att, cache = _attn_train(cfg, p, xn, ctx, cache)
        att = _psum(att, ctx.tp_axis)
        if cfg.hybrid_parallel:
            sy, cache = _ssd_branch(cfg, {k[4:]: v for k, v in p.items()
                                          if k.startswith("ssd_")}, xn, ctx,
                                    cache)
            att = 0.5 * (rms_norm(att, p["bn_attn"], eps=cfg.norm_eps)
                         + rms_norm(sy, p["bn_ssm"], eps=cfg.norm_eps))
        if cfg.norm_style == "rms_sandwich":
            att = rms_norm(att, p["ln1_post"], eps=cfg.norm_eps)
        x = x + att
        xn2 = _norm(cfg, p, "ln2", x)
        if cfg.num_experts:
            m, aux = _mlp_moe(cfg, p, xn2, ctx.tp_axis)
        else:
            m = _psum(_mlp_dense(cfg, p, xn2), ctx.tp_axis)
        if cfg.norm_style == "rms_sandwich":
            m = rms_norm(m, p["ln2_post"], eps=cfg.norm_eps)
        x = x + m

    valid = jnp.asarray(ctx.valid)
    x = jnp.where(valid, x, x_in)
    aux = jnp.where(valid, aux, 0.0)
    return x, cache, aux
