"""The pipelined wave step — HetPipe's virtual-worker PMP in SPMD JAX.

One jitted call processes one *wave* (Nm minibatches) through the pipeline:
  - the `model` mesh axis hosts stage x tp (paper: the k GPUs of a virtual
    worker); stages exchange boundary activations with lax.ppermute inside a
    scan over pipeline ticks (Nm + stages - 1 ticks; bubble ticks execute
    masked garbage, so compiled HLO FLOPs honestly include the pipeline bubble)
  - `data` (x `pod`) axes index virtual workers; the wave-aggregated update is
    reduced across them once per wave (WSP's per-wave sync; D=0 in SPMD — the
    threaded runtime provides true-async D>0 via the parameter server)

All microbatch packing/unpacking happens VW-locally inside the shard_map body,
so no global resharding is introduced around the pipeline. The same machinery
drives train (AD through the pipeline scan), prefill and decode (fwd-only,
KV/SSM caches updated in the scan carry).
"""
from __future__ import annotations

import functools
import threading
from dataclasses import replace as dc_replace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, RunConfig
from repro.models import lm
from repro.models.blocks import LayerCtx, apply_layer
from repro.models.layers import chunked_cross_entropy
from repro.obs import current_tracer
from repro.optim import make_optimizer
from repro.serve import cache as cache_lib

S_AX, T_AX, D_AX = "stage", "tp", "data"


def dp_axes(mesh: Mesh):
    return tuple(a for a in ("pod", D_AX) if a in mesh.axis_names)


def tick_schedule(stages: int, nm: int, *, overlap: bool = False
                  ) -> tuple[list, int]:
    """The pipeline schedule pipeline_wave executes, as data: a list of
    (stage, tick, mb) entries — mb = -1 for bubble ticks — plus the tick
    count. Microbatch j reaches stage s at tick j + skew*s (skew 2 under
    the software-pipelined overlap schedule, else 1), exactly the mb_idx
    arithmetic in pipeline_wave.tick. Observability renders this as
    per-stage trace tracks; bubble fraction = 1 - nm*stages/len(entries)."""
    skew = 2 if overlap else 1
    ticks = nm + skew * (stages - 1)
    sched = []
    for s in range(stages):
        for t in range(ticks):
            mb = t - skew * s
            sched.append((s, t, mb if 0 <= mb < nm else -1))
    return sched, ticks


def n_dp(mesh: Mesh) -> int:
    axes = dp_axes(mesh)
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


# ----------------------------------------------------------------------------
# per-device pipeline (called inside shard_map)
# ----------------------------------------------------------------------------
def _stage_apply(cfg, blocks_local, x, meta_arrs, ctx: LayerCtx, cache_local):
    """Unrolled layer slots with tick-validity threaded into each layer."""
    aux = jnp.zeros((), jnp.float32)
    uk = lm.uniform_kind(cfg)
    base_valid = ctx.valid
    for s in range(cfg.layer_slots):
        p_l = jax.tree.map(lambda a: a[s], blocks_local)
        ctx_s = dc_replace(
            ctx,
            kind=uk if uk is not None else meta_arrs["kind"][s],
            valid=base_valid if uk is not None
            else jnp.logical_and(base_valid, meta_arrs["valid"][s]),
            full_i=meta_arrs["full_i"][s],
            win_i=meta_arrs["win_i"][s],
            ssm_i=s,
        )
        x, cache_local, a = apply_layer(cfg, p_l, x, ctx_s, cache_local)
        aux = aux + a
    return x, cache_local, aux


def pipeline_wave(cfg: ArchConfig, blocks_local, x_local, meta_local, *,
                  mode: str, nm: int, cache_local=None, pos=None, lens=None,
                  tp_axis: Optional[str], merge_axis: Optional[str],
                  seq_offset=0, remat: bool = False, overlap: bool = False,
                  kernel_backend: str = "ref"):
    """x_local [Bl, S, d] (this VW's wave batch). Returns (y [Bl,S,d] — valid
    on the last stage — cache_local, aux).

    overlap=False is the baseline (oracle) schedule: each tick computes and
    then ppermutes its output, so the boundary transfer sits on the critical
    path between consecutive stages.

    overlap=True is the software-pipelined (skewed) schedule: each tick
    computes from the buffer *received last tick* while ppermuting the output
    computed *last tick* — the two ops have no data dependence inside a tick,
    so the compiler's latency-hiding scheduler can run the collective
    concurrently with stage compute. The price is one extra tick of skew per
    stage boundary (ticks = nm + 2(k-1) instead of nm + k-1): microbatch j
    reaches stage s at tick j + 2s. Per-microbatch compute is identical, so
    losses/grads match the oracle bit-for-bit."""
    stages = cfg.stages
    si = jax.lax.axis_index(S_AX)
    Bl, S, d = x_local.shape
    mb = Bl // nm
    x_wave = x_local.reshape(nm, mb, S, d)
    meta_arrs = {k: meta_local[k][0] for k in
                 ("kind", "valid", "full_i", "win_i")}          # [slots]
    skew = 2 if overlap else 1
    ticks = nm + skew * (stages - 1)
    perm = [(i, i + 1) for i in range(stages - 1)]

    def stage_call(x_in, cache_mb, tick_valid, pos_, lens_=None):
        ctx = LayerCtx(mode=mode, pos=pos_, tp_axis=tp_axis,
                       merge_axis=merge_axis, seq_offset=seq_offset,
                       valid=tick_valid, lens=lens_,
                       kernel_backend=kernel_backend)
        return _stage_apply(cfg, blocks_local, x_in, meta_arrs, ctx, cache_mb)

    stage_fn = jax.checkpoint(stage_call) if (remat and mode == "train") \
        else stage_call

    def tick(carry, t):
        if overlap:
            buf_in, y_send, out, cache_c, aux = carry
        else:
            buf_in, out, cache_c, aux = carry
            y_send = None
        mb_idx = t - skew * si
        valid = (mb_idx >= 0) & (mb_idx < nm)
        mb_c = jnp.clip(mb_idx, 0, nm - 1)
        x_fresh = jax.lax.dynamic_index_in_dim(x_wave, mb_c, 0, keepdims=False)
        x_in = jnp.where(si == 0, x_fresh, buf_in)
        # per-row decode positions / prompt lengths ([Bl] vectors) slice
        # with the microbatch, like the cache; a scalar pos is shared
        pos_mb = (jax.lax.dynamic_slice_in_dim(pos, mb_c * mb, mb)
                  if pos is not None and jnp.ndim(pos) == 1 else pos)
        lens_mb = (jax.lax.dynamic_slice_in_dim(lens, mb_c * mb, mb)
                   if lens is not None else None)
        if cache_c is None:
            y, _, aux_t = stage_fn(x_in, None, valid, pos_=pos_mb,
                                   lens_=lens_mb)
        else:
            # serve path (no AD): bubble ticks skip the cache read/write and
            # the stage compute entirely — otherwise every dead tick pays the
            # full cache-slice HBM traffic ((nm+k-1)/nm x minimal bytes;
            # measured 2.9x for decode_32k at nm=8 — EXPERIMENTS.md §Perf)
            def live(cc):
                cm = cache_lib.slice_mb(cc, mb_c, mb)
                y_, new_cm, a_ = stage_fn(x_in, cm, valid, pos_=pos_mb,
                                          lens_=lens_mb)
                cc = cache_lib.update_mb(cc, new_cm, mb_c, mb, valid)
                return cc, y_, a_

            def dead(cc):
                return cc, jnp.zeros_like(x_in), jnp.zeros((), jnp.float32)

            cache_c, y, aux_t = jax.lax.cond(valid, live, dead, cache_c)
        aux = aux + jnp.where(valid, aux_t, 0.0)
        out_idx = t - skew * (stages - 1)
        w_valid = (si == stages - 1) & (out_idx >= 0) & (out_idx < nm)
        oc = jnp.clip(out_idx, 0, nm - 1)
        old = jax.lax.dynamic_index_in_dim(out, oc, 0, keepdims=False)
        out = jax.lax.dynamic_update_index_in_dim(
            out, jnp.where(w_valid, y, old), oc, 0)
        if overlap:
            # double-buffered carry: send last tick's output (no dependence
            # on this tick's stage_fn, so the transfer overlaps the compute);
            # it is consumed by the next stage one tick after arrival, i.e.
            # two ticks after it was computed — matching the 2-tick skew.
            buf_next = jax.lax.ppermute(y_send, S_AX, perm)
            return (buf_next, y, out, cache_c, aux), None
        buf_next = jax.lax.ppermute(y, S_AX, perm)
        return (buf_next, out, cache_c, aux), None

    buf0 = jnp.zeros((mb, S, d), x_local.dtype)
    out0 = jnp.zeros_like(x_wave)
    aux0 = jnp.zeros((), jnp.float32)
    carry0 = ((buf0, jnp.zeros_like(buf0), out0, cache_local, aux0)
              if overlap else (buf0, out0, cache_local, aux0))
    final_carry, _ = jax.lax.scan(tick, carry0, jnp.arange(ticks))
    out, cache_local, aux = final_carry[-3], final_carry[-2], final_carry[-1]
    return out.reshape(Bl, S, d), cache_local, aux


# ----------------------------------------------------------------------------
# spec assembly
# ----------------------------------------------------------------------------
def _meta_tree(cfg: ArchConfig):
    m = lm.layer_meta(cfg)
    arrs = {k: jnp.asarray(m[k]) for k in ("kind", "valid", "full_i", "win_i")}
    specs = {k: P(S_AX, None) for k in arrs}
    return arrs, specs


def _cast_tree(tree, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, tree)


def _loss_over_wave(cfg, run, params, hid, labels):
    """hid [B, S, d] paired row-for-row with labels [B, S]."""
    h = lm.final_hidden_norm(cfg, params, hid)
    return chunked_cross_entropy(
        h, lm.head_matrix(cfg, params), labels,
        chunk=min(run.loss_chunk, h.shape[-2]))


# ----------------------------------------------------------------------------
# train step
# ----------------------------------------------------------------------------
def build_train_step(run: RunConfig, mesh: Mesh):
    """Returns (train_step, state_specs) where
    train_step(params, opt_state, batch{'inputs','labels'}) ->
        (params, opt_state, metrics)."""
    cfg = run.arch
    assert cfg.stages == mesh.shape[S_AX], (cfg.stages, dict(mesh.shape))
    assert cfg.tp in (1, mesh.shape[T_AX]), (cfg.tp, dict(mesh.shape))
    nm = cfg.num_microbatches
    meta_arrs, meta_specs = _meta_tree(cfg)
    pspecs = lm.param_specs(cfg)
    tp_axis = T_AX if cfg.tp > 1 else None
    cdt = jnp.bfloat16 if run.compute_dtype == "bfloat16" else jnp.float32
    opt = make_optimizer(run.optimizer, run.lr, run.weight_decay)
    dp = dp_axes(mesh)

    def body(blocks, x, meta):
        y, _, aux = pipeline_wave(
            cfg, blocks, x, meta, mode="train", nm=nm, tp_axis=tp_axis,
            merge_axis=None, remat=cfg.remat, overlap=run.overlap)
        aux = jax.lax.psum(aux, S_AX)      # each stage holds its layers' aux
        for ax in dp:                      # aux differs per VW's tokens
            aux = jax.lax.pmean(aux, ax)
        # the CE head is vocab-sharded over (stage, tp): every model device
        # needs the final hidden anyway, so this masked psum doubles as the
        # hidden broadcast GSPMD would otherwise insert for the loss.
        return _bcast_from_last(y, cfg.stages), aux / nm

    pipe = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs["blocks"], P(dp, None, None), meta_specs),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )

    def wave_loss(params, inputs, labels):
        x = lm.embed_tokens(cfg, params, inputs).astype(cdt)
        y, aux = pipe(_cast_tree(params["blocks"], cdt), x, meta_arrs)
        loss = _loss_over_wave(cfg, run, params, y, labels)
        total = loss + 0.01 * aux / max(cfg.num_layers, 1)
        return total, (total, aux)

    def train_step(params, opt_state, batch):
        (_, (loss, aux)), grads = jax.value_and_grad(
            wave_loss, has_aux=True)(params, batch["inputs"], batch["labels"])
        deltas, opt_state = opt.update(grads, opt_state, params)
        params = jax.tree.map(jnp.add, params, deltas)
        return params, opt_state, {"loss": loss, "aux": aux}

    state_specs = {"params": pspecs,
                   "batch": {"inputs": P(dp, *([None] * (2 if cfg.frontend ==
                                                "none" else 3))[1:]),
                             "labels": P(dp, None)},
                   "opt": None, "meta": meta_arrs, "optimizer": opt}
    return train_step, state_specs


# NOTE on out_specs of the pipeline: the per-device output y [Bl, S, d] is
# only meaningful on the last stage; out_specs P(dp, None, None) declares it
# replicated over stage/tp, and check_vma=False lets XLA pick the last stage's
# copy... which is NOT guaranteed. We therefore broadcast the last stage's
# value inside the body — see _bcast_from_last below, applied in pipeline_wave
# callers via _finalize_out.


def _bcast_from_last(y, stages):
    """Make y consistent across stages: everyone gets the last stage's copy
    via a single ppermute hop ring (last -> all through rotation is O(k) hops;
    instead use psum of masked value — one all-reduce over the stage axis)."""
    si = jax.lax.axis_index(S_AX)
    contrib = jnp.where(si == stages - 1, y, jnp.zeros_like(y))
    return jax.lax.psum(contrib, S_AX)


# ----------------------------------------------------------------------------
# serve steps (prefill / decode)
# ----------------------------------------------------------------------------
def _serve_nm(run: RunConfig, mesh) -> tuple[int, int]:
    cfg, shp = run.arch, run.shape
    vw_b = max(1, shp.global_batch // n_dp(mesh))
    nm = min(cfg.num_microbatches, vw_b)
    while vw_b % nm:
        nm -= 1
    return nm, vw_b // nm


def build_decode_step(run: RunConfig, mesh: Mesh, *,
                      pos_per_row: bool = False, layout=None):
    """step(params, batch{'inputs','cache','pos'}) -> (logits, cache).

    pos_per_row=True: batch['pos'] is a [B] vector — each batch row decodes
    at its own depth (continuous batching; rows at different generation
    depths share one jitted step). Requires an unsharded batch (data=1);
    the default scalar pos is the aligned-batch fast path.

    layout: a repro.serve.cache.PageLayout — the cache pytree is the paged
    pool + block table instead of the contiguous block (full-attention K/V
    read through the table; the pool rides the pipeline scan whole)."""
    cfg, shp = run.arch, run.shape
    nm, _ = _serve_nm(run, mesh)
    meta_arrs, meta_specs = _meta_tree(cfg)
    pspecs = lm.param_specs(cfg)
    tp_axis = T_AX if cfg.tp > 1 else None
    seq_sharded = (layout is None and shp.global_batch < 16
                   and D_AX in mesh.axis_names)
    merge_axis = D_AX if seq_sharded else None
    cdt, cache_dt = lm.serve_dtypes(run.compute_dtype, run.cache_dtype)
    if layout is not None:
        _, cspecs = cache_lib.paged_struct(cfg, layout, dtype=cache_dt)
    else:
        _, cspecs = cache_lib.cache_struct(
            cfg, shp.global_batch, shp.seq_len,
            seq_shards=16 if seq_sharded else 1, dtype=cache_dt)
    dp = dp_axes(mesh) if not seq_sharded else ()
    nd = mesh.shape[D_AX] if D_AX in mesh.axis_names else 1
    if pos_per_row and n_dp(mesh) != 1:
        raise ValueError("pos_per_row decode needs the whole batch on every "
                         "data shard; use a data=1 mesh")
    if layout is not None and n_dp(mesh) != 1:
        raise ValueError("the paged pool is shared by the whole batch; "
                         "paged decode needs a data=1 mesh")
    pos_spec = P(None) if pos_per_row else P()

    def body(blocks, x, meta, cache, pos):
        so = jax.lax.axis_index(D_AX) * (shp.seq_len // nd) if seq_sharded \
            else 0
        y, cache, aux = pipeline_wave(
            cfg, blocks, x, meta, mode="decode", nm=nm, cache_local=cache,
            pos=pos, tp_axis=tp_axis, merge_axis=merge_axis, seq_offset=so,
            overlap=run.overlap, kernel_backend=run.kernel_backend)
        return _bcast_from_last(y, cfg.stages), cache, aux

    pipe = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs["blocks"], P(dp, None, None), meta_specs, cspecs,
                  pos_spec),
        out_specs=(P(dp, None, None), cspecs, P()),
        check_vma=False,
    )

    def decode_step(params, batch):
        x = lm.embed_tokens(cfg, params, batch["inputs"]).astype(cdt)
        logits_hid, cache, _ = pipe(_cast_tree(params["blocks"], cdt), x,
                                    meta_arrs, batch["cache"], batch["pos"])
        logits = lm.logits_ref(cfg, params, logits_hid)
        return logits, cache

    return decode_step, pspecs, cspecs


def build_prefill_step(run: RunConfig, mesh: Mesh, *, cache_len: int = 0,
                       layout=None, var_len: bool = False):
    """step(params, batch{'inputs','cache'[,'lens']}) -> (last_logits, cache).

    cache_len > shp.seq_len sizes the cache for the decode phase that
    follows prefill (serve: prompt_len inputs, prompt_len + gen cache slots;
    the prefill write zero-pads the unwritten tail).

    layout: PageLayout — prefill scatters K/V page-granularly through
    batch['cache']'s block table instead of filling contiguous rows.
    var_len=True: batch['lens'] is a [B] vector of per-row prompt lengths
    (right-padded prompts); cache writes stop at each row's length and the
    returned logits are each row's *last real* position."""
    cfg, shp = run.arch, run.shape
    nm, _ = _serve_nm(run, mesh)
    meta_arrs, meta_specs = _meta_tree(cfg)
    pspecs = lm.param_specs(cfg)
    tp_axis = T_AX if cfg.tp > 1 else None
    cdt, cache_dt = lm.serve_dtypes(run.compute_dtype, run.cache_dtype)
    if layout is not None:
        _, cspecs = cache_lib.paged_struct(cfg, layout, dtype=cache_dt)
    else:
        _, cspecs = cache_lib.cache_struct(cfg, shp.global_batch,
                                           cache_len or shp.seq_len,
                                           dtype=cache_dt)
    if (layout is not None or var_len) and n_dp(mesh) != 1:
        # mirrors build_decode_step: the paged pool (and the per-row lens
        # vector) address the whole batch; a data-sharded x would pair
        # shard-local rows with global lens/table rows silently
        raise ValueError("paged / variable-length prefill needs the whole "
                         "batch on every data shard; use a data=1 mesh")
    dp = dp_axes(mesh)

    def body(blocks, x, meta, cache, lens=None):
        y, cache, aux = pipeline_wave(
            cfg, blocks, x, meta, mode="prefill", nm=nm, cache_local=cache,
            pos=None, lens=lens, tp_axis=tp_axis, merge_axis=None,
            overlap=run.overlap, kernel_backend=run.kernel_backend)
        if lens is None:
            last = y[:, -1:]
        else:
            last = jnp.take_along_axis(
                y, jnp.maximum(lens - 1, 0)[:, None, None], axis=1)
        return _bcast_from_last(last, cfg.stages), cache, aux

    in_specs = [pspecs["blocks"], P(dp, None, None), meta_specs, cspecs]
    if var_len:
        in_specs.append(P(None))
    pipe = jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(dp, None, None), cspecs, P()),
        check_vma=False,
    )

    def prefill_step(params, batch):
        x = lm.embed_tokens(cfg, params, batch["inputs"]).astype(cdt)
        args = (_cast_tree(params["blocks"], cdt), x, meta_arrs,
                batch["cache"])
        if var_len:
            args += (batch["lens"],)
        last_hid, cache, _ = pipe(*args)
        logits = lm.logits_ref(cfg, params, last_hid)
        return logits, cache

    return prefill_step, pspecs, cspecs


# ----------------------------------------------------------------------------
# single-device wave step (per-VW; used by the threaded WSP runtime and as
# the pipeline-correctness oracle: a wave == grad accumulation over Nm
# minibatches computed with wave-start weights)
# ----------------------------------------------------------------------------
def build_local_wave_step(cfg: ArchConfig, nm: int, optimizer):
    """Gradients are summed microbatch by microbatch inside the scan, so
    only one microbatch's activations are live at a time (differentiating
    through a scan of losses would keep all Nm of them for the backward).

    The threaded runtime's virtual workers share this step and the
    process's device, so calls take turns and return the deltas on the
    host: device memory then holds one wave's buffers at a time (two
    concurrent qwen3-0.6b waves need more than a 16 GB TPU v5e has), and
    the device runs one program at a time anyway."""
    def mb_loss(params, x_mb, l_mb):
        loss, _, _ = lm.forward_ref(cfg, params, x_mb, mode="train",
                                    labels=l_mb)
        return loss

    mb_grad = jax.value_and_grad(mb_loss)

    @jax.jit
    def wave_step(params, opt_state, inputs, labels):
        def body(carry, xs):
            total, gsum = carry
            loss, g = mb_grad(params, *xs)
            return (total + loss, jax.tree.map(jnp.add, gsum, g)), None

        B = labels.shape[0]
        xw = inputs.reshape(nm, B // nm, *inputs.shape[1:])
        lw = labels.reshape(nm, B // nm, labels.shape[1])
        carry0 = (jnp.zeros((), jnp.float32),
                  jax.tree.map(jnp.zeros_like, params))
        (total, gsum), _ = jax.lax.scan(body, carry0, (xw, lw))
        grads = jax.tree.map(lambda g: g / nm, gsum)
        deltas, opt_state = optimizer.update(grads, opt_state, params)
        return deltas, opt_state, total / nm

    lock = threading.Lock()

    def locked_step(params, opt_state, inputs, labels):
        # the caller's tracer (the worker's `wave` span), in four spans:
        # the wait for the device, the call (its numpy arguments go to the
        # device), the device's work (waited for only when traced) and
        # the deltas' copy to the host
        tr = current_tracer()
        with tr.span("wave_step", "lock_wait"):
            lock.acquire()
        try:
            with tr.span("wave_step", "to_device"):
                deltas, opt_state, loss = wave_step(params, opt_state,
                                                    inputs, labels)
            if tr.enabled:
                with tr.span("wave_step", "compute"):
                    jax.block_until_ready(deltas)
            with tr.span("wave_step", "to_host"):
                deltas = jax.device_get(deltas)
        finally:
            lock.release()
        return deltas, opt_state, loss

    return locked_step
