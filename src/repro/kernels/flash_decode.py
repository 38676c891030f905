"""Pallas TPU flash-decoding: one query token vs. a long KV cache.

Two entry points share the online-softmax inner loop:

  flash_decode        contiguous KV [B, KV, S, hd]; `length` is a scalar or
                      a per-row [B] vector (continuous batching: each row at
                      its own depth). Cache lengths that do not divide the
                      k-block are padded with dead (masked) positions up to
                      a block multiple instead of silently shrinking the
                      block toward 1 (which destroyed MXU alignment for
                      prime cache lengths).
  flash_decode_paged  the serve KV pool [groups, num_pages+1, KV,
                      page_size, hd] indexed *in the kernel* through the
                      per-slot int32 block table: the table and the per-row
                      lengths ride as scalar-prefetch operands and the
                      table drives the pool BlockSpec index map, so batched
                      decode at mixed depths never materializes a
                      contiguous per-row KV view (`CacheStore.gather_view`
                      / `cache.page_view` stay debug-only). Unmapped table
                      entries (-1) resolve to the trash page and are
                      masked; rows with length == 0 emit zeros. Grid
                      (b, head block, page block); see paged_blocks.

flash_decode's grid is (b, kv_head, k_block), k_block innermost; the GQA
group's G query rows ride together as a [G, hd] tile (G <= 8 for the
assigned archs — a VPU-sized tile; the matmuls are [G,hd]x[hd,bk],
MXU-aligned on bk and hd). Accumulators (m, l, acc over G rows) persist in
VMEM scratch; blocks beyond the row's `length` (the current cache fill) or
outside the sliding window are skipped with pl.when — decode cost scales
with the live cache, not the allocated one. Fully-masked rows
(length == 0) emit zeros: the contract
`kernels/ref.py:decode_ref` mirrors.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

logger = logging.getLogger(__name__)


def _row_lengths(length, B):
    """Scalar or [B] -> [B] int32 per-row lengths."""
    lens = jnp.asarray(length, jnp.int32).reshape(-1)
    if lens.shape[0] not in (1, B):
        raise ValueError(
            f"length must be a scalar or a [B]={B} vector, got "
            f"shape {jnp.asarray(length).shape}")
    return jnp.broadcast_to(lens, (B,))


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                   scale, window, bk, nk):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    k_start = ki * bk
    length = len_ref[b]

    @pl.when(ki == 0)
    def _reset():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    live = k_start < length
    if window > 0:
        live = jnp.logical_and(live, k_start + bk > length - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [G, hd]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        gk = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = gk < length
        if window > 0:
            mask &= gk >= length - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot(p, v)
        m_sc[...] = m_new

    @pl.when(ki == nk - 1)
    def _write():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


def flash_decode(q1, k, v, length, *, window=0, block_k=256,
                 interpret=False):
    """q1 [B,H,hd]; k,v [B,KV,S,hd]; length scalar or [B] int32 (tokens live
    in each row's cache). Rows with length == 0 return zeros. Returns
    [B,H,hd]."""
    B, H, hd = q1.shape
    KV, S = k.shape[1], k.shape[2]
    G = H // KV
    bk = min(block_k, S)
    if S % bk:
        # pad the KV view with dead positions up to a block multiple (they
        # sit at gk >= S >= length, so the length mask kills them) rather
        # than shrinking bk toward 1 and destroying MXU alignment
        pad = bk - S % bk
        logger.warning(
            "flash_decode: cache length %d is not a multiple of block_k=%d; "
            "padding %d dead (masked) positions instead of degrading the "
            "block size", S, bk, pad)
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        S += pad
    nk = S // bk
    qg = q1.reshape(B, KV, G, hd)
    lens = _row_lengths(length, B)

    kernel = functools.partial(_decode_kernel, scale=hd ** -0.5,
                               window=window, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q1.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(lens, qg, k, v)
    return out.reshape(B, H, hd)


# ----------------------------------------------------------------------------
# Paged decode: the block-table walk fused into the BlockSpec index map
# ----------------------------------------------------------------------------
#: bytes of double-buffered K + V blocks one paged grid step may keep in
#: VMEM (half the 16 MiB default scoped limit of a v5e core; the rest holds
#: q/out tiles, f32 upcasts of one head's block and the accumulators)
PAGED_VMEM_BUDGET = 8 * 1024 * 1024

#: second-minor tile of each KV dtype's itemsize: a sub-page block must be a
#: multiple of it
_SUBLANES = {4: 8, 2: 16, 1: 32}


def paged_blocks(KV: int, ps: int, hd: int, itemsize: int) -> tuple[int, int]:
    """(kv heads per block, positions per block) for the paged kernel.

    A block holds `hb` KV heads of one page (or of a `bps`-position slice
    of it); its last two dims are (bps, hd), so a whole page is legal at
    any page size. The widest block that fits PAGED_VMEM_BUDGET wins;
    raises when even one head of the smallest aligned slice cannot."""
    def fits(hb, bps):
        return 4 * hb * bps * hd * itemsize <= PAGED_VMEM_BUDGET

    for hb in (h for h in range(KV, 0, -1) if KV % h == 0):
        if fits(hb, ps):
            return hb, ps
    tile = _SUBLANES.get(itemsize, 8)
    for bps in range(ps - ps % tile, 0, -tile):
        if ps % bps == 0 and fits(1, bps):
            return 1, bps
    raise ValueError(
        f"paged decode: one KV head of a {ps}-token page ({hd} x "
        f"{itemsize}-byte lanes) exceeds the {PAGED_VMEM_BUDGET}-byte VMEM "
        f"budget and the page has no {tile}-aligned divisor that fits; use "
        f"a smaller page_size")


def _paged_kernel(lay_ref, tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_sc, l_sc, acc_sc, *, scale, ps, bps, nsub, nblk, hb):
    b = pl.program_id(0)
    j = pl.program_id(2)
    length = len_ref[b]

    @pl.when(j == 0)
    def _reset():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    pi = j // nsub
    start = pi * ps + (j % nsub) * bps
    # blocks at/after the row's fill or on unmapped (-1 -> trash) pages are
    # dead; skipping them keeps decode cost proportional to the live cache
    live = jnp.logical_and(start < length, tab_ref[b, pi] >= 0)

    @pl.when(live)
    def _compute():
        for h in range(hb):
            q = q_ref[0, h].astype(jnp.float32)              # [G, hd]
            k = k_ref[0, 0, h].astype(jnp.float32)           # [bps, hd]
            v = v_ref[0, 0, h].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
            gk = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = gk < length
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_sc[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_sc[h] = l_sc[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[h] = acc_sc[h] * corr + jax.lax.dot(p, v)
            m_sc[h] = m_new

    @pl.when(j == nblk - 1)
    def _write():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)


def flash_decode_paged(q1, k_pool, v_pool, block_tab, lengths, *, layer=0,
                       interpret=False):
    """Paged flash-decode over the serve pool layout (see repro.serve.cache).

    q1 [B, H, hd]; k_pool/v_pool [groups, num_pages+1, KV, page_size, hd]
    (head-major pages; last page = trash); block_tab [B, pages_per_slot]
    int32, -1 = unmapped; lengths scalar or [B] int32 (tokens live per row);
    layer = the group index to read (scalar, may be traced). Returns
    [B, H, hd]; rows with length == 0 return zeros.

    The walk is fused: block_tab/lengths/layer ride as scalar-prefetch
    operands and the pool BlockSpec index map resolves the physical page per
    (row, head block, logical page) grid cell, so nothing gathers the pool
    into a contiguous [B, S, KV, hd] view. Each block carries `hb` KV heads
    of a page (see paged_blocks), so its last two dims (positions, hd) are
    whole and legal for Mosaic at any page size.
    """
    B, H, hd = q1.shape
    groups, P1, KV, ps, _ = k_pool.shape
    trash = P1 - 1
    npg = block_tab.shape[1]
    G = H // KV
    hb, bps = paged_blocks(KV, ps, hd, jnp.dtype(k_pool.dtype).itemsize)
    nsub = ps // bps
    nblk = npg * nsub
    qg = q1.reshape(B, KV, G, hd)
    tab = jnp.asarray(block_tab, jnp.int32)
    lens = _row_lengths(lengths, B)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)

    def pool_map(b, hi, j, lay_ref, tab_ref, len_ref):
        t = tab_ref[b, j // nsub]
        return (lay_ref[0], jnp.where(t >= 0, t, trash), hi, j % nsub, 0)

    def q_map(b, hi, j, *_):
        return (b, hi, 0, 0)

    kernel = functools.partial(_paged_kernel, scale=hd ** -0.5, ps=ps,
                               bps=bps, nsub=nsub, nblk=nblk, hb=hb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV // hb, nblk),
        in_specs=[
            pl.BlockSpec((1, hb, G, hd), q_map),
            pl.BlockSpec((1, 1, hb, bps, hd), pool_map),
            pl.BlockSpec((1, 1, hb, bps, hd), pool_map),
        ],
        out_specs=pl.BlockSpec((1, hb, G, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((hb, G, 1), jnp.float32),
            pltpu.VMEM((hb, G, 1), jnp.float32),
            pltpu.VMEM((hb, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q1.dtype),
        interpret=interpret,
    )(lay, tab, lens, qg, k_pool, v_pool)
    return out.reshape(B, H, hd)
