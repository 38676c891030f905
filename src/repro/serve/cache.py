"""The serve-side cache subsystem: every byte of KV/SSM decoding state.

HetPipe's premise is that per-stage memory is the scarce resource, so the
serve path treats its cache as a managed, accounted object rather than a
worst-case contiguous block. This module is the single owner of cache
layout knowledge; everything else (models.blocks, core.wave, the Engine,
the Scheduler) goes through its API.

Two layouts, one API:

  contiguous   today's `[groups, batch, max_len, KV, hd]` block — the
               reference implementation. `page_size == max_len` paging
               degenerates to it (one page per slot). `cache_struct` /
               `init_cache` build it; `lm.cache_struct` delegates here.

  paged        full-attention K/V live in a fixed pool of head-major
               pages `[groups, num_pages + 1, KV, page_size, hd]` (the
               extra page is a write-off target for unmapped slots; each
               page's per-head [page_size, hd] tile is a whole block for
               the paged decode kernel at any page size) indexed
               through a per-slot block table `block_tab [max_batch,
               pages_per_slot]` (−1 = unmapped). Reads gather a per-row
               page view; writes scatter page-granularly. Fixed-size
               per-slot state (sliding-window ring, SSM/RWKV recurrent
               state, conv/shift tails) keeps the batch-dim layout — it
               does not grow with sequence length, so paging it would buy
               nothing.

`CacheStore` owns the device tree plus host-side page accounting:
`alloc(slot, tokens)` / `free(slot)` move pages between the free list and
a slot's block-table row, `can_alloc` is the Scheduler's admission gate,
`append_rows` absorbs a prefill step's output (page pool wholesale,
per-slot rows copied into their assigned slots), `gather_view` returns the
per-row contiguous view + positions for host-side inspection, and
`stats()` reports page utilization and bytes (the honest per-stage HBM
number the partitioner can price).

Pages are **refcounted**: `alloc(slot, tokens, shared=pages)` maps an
already-resident prefix (another slot's pages, or cold indexed ones) into
the new slot's block table and only draws the remainder from the free
list — the mechanics under `repro.serve.memory`'s prefix sharing. A page
returns to the free list when its last mapping drops *and* no index hold
(`retain`/`release`) keeps it resident; `copy_page` is the copy-on-write
primitive; `last_touch` carries the LRU stamp the eviction policy sorts
by. `pages_in_use` counts **distinct** physical pages — a page mapped
into five block tables is one page of HBM, not five.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

S_AX, T_AX, D_AX = "stage", "tp", "data"

#: per-slot (batch-dim) cache keys — everything that is not the paged pool
#: or the block table. Layout: batch at dim 1 of every leaf.
SLOT_KEYS = ("kv_win", "ssm_state", "conv_tail", "shift")


# ----------------------------------------------------------------------------
# dtypes
# ----------------------------------------------------------------------------
def serve_dtypes(compute_dtype: str, cache_dtype: str = ""):
    """Resolve the string knobs shared by RunConfig/ServeSpec to
    (compute jnp dtype, cache jnp dtype): compute 'bfloat16' | 'float32';
    cache '' (= compute dtype) or 'f8' (fp8 KV). One mapping for every
    consumer (wave steps, input specs, the Engine serve path, CacheStore),
    so a new cache dtype cannot drift between the allocator and the
    compiled step."""
    cdt = jnp.bfloat16 if compute_dtype == "bfloat16" else jnp.float32
    return cdt, {"f8": jnp.float8_e4m3fn, "": cdt}.get(cache_dtype, cdt)


# ----------------------------------------------------------------------------
# contiguous layout (the reference implementation)
# ----------------------------------------------------------------------------
def cache_struct(cfg, batch: int, max_len: int, *, seq_shards: int = 1,
                 dtype=jnp.bfloat16):
    """Returns (cache_shapes pytree of ShapeDtypeStruct, specs pytree).

    Cache group layout (global):
      kv_full [stages*m_full, B, S, KV, hd]   (seq possibly sharded over data)
      kv_win  [stages*m_win,  B, W, KV, hd]
      ssm_state [Lp, B, H, K, P] fp32 ; conv_tail/shift small
    """
    from repro.models.lm import layer_meta
    meta = layer_meta(cfg)
    st = cfg.stages
    Lp = cfg.padded_layers
    kv_tp = T_AX if (cfg.num_kv_heads and cfg.tp > 1
                     and cfg.num_kv_heads % cfg.tp == 0) else None
    batch_ax = D_AX if batch >= 16 else None
    seq_ax = D_AX if seq_shards > 1 else None
    shapes, specs = {}, {}
    hd, KV = cfg.head_dim, cfg.num_kv_heads
    if meta["m_full"] > 0 and cfg.attn_type != "none":
        shp = (st * meta["m_full"], batch, max_len, KV, hd)
        shapes["kv_full"] = tuple(jax.ShapeDtypeStruct(shp, dtype)
                                  for _ in range(2))
        specs["kv_full"] = tuple(P(S_AX, batch_ax, seq_ax, kv_tp, None)
                                 for _ in range(2))
    if meta["m_win"] > 0:
        W = min(cfg.window_size, max_len)
        shp = (st * meta["m_win"], batch, W, KV, hd)
        shapes["kv_win"] = tuple(jax.ShapeDtypeStruct(shp, dtype)
                                 for _ in range(2))
        specs["kv_win"] = tuple(P(S_AX, batch_ax, None, kv_tp, None)
                                for _ in range(2))
    if cfg.ssm_type == "ssd":
        H, N, Pd = cfg.n_ssm_heads, cfg.ssm_state, cfg.d_inner // cfg.n_ssm_heads
        shapes["ssm_state"] = jax.ShapeDtypeStruct((Lp, batch, H, N, Pd),
                                                   jnp.float32)
        specs["ssm_state"] = P(S_AX, batch_ax, None, None, None)
        shapes["conv_tail"] = jax.ShapeDtypeStruct(
            (Lp, batch, 3, cfg.d_inner + 2 * N), dtype)
        specs["conv_tail"] = P(S_AX, batch_ax, None, None)
    if cfg.ssm_type == "rwkv6":
        H = cfg.n_ssm_heads
        hds = cfg.d_model // H
        shapes["ssm_state"] = jax.ShapeDtypeStruct((Lp, batch, H, hds, hds),
                                                   jnp.float32)
        specs["ssm_state"] = P(S_AX, batch_ax, None, None, None)
        shapes["shift"] = jax.ShapeDtypeStruct((Lp, batch, 2, cfg.d_model),
                                               dtype)
        specs["shift"] = P(S_AX, batch_ax, None, None)
    return shapes, specs


def init_cache(cfg, batch: int, max_len: int, *, seq_shards=1,
               dtype=jnp.bfloat16):
    shapes, _ = cache_struct(cfg, batch, max_len, seq_shards=seq_shards,
                             dtype=dtype)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


# ----------------------------------------------------------------------------
# paged layout
# ----------------------------------------------------------------------------
@dataclass(frozen=True)
class PageLayout:
    """Static geometry of a paged cache pool."""

    max_batch: int
    max_len: int                # logical positions per slot (prompt + gen)
    page_size: int              # tokens per page
    num_pages: int              # usable physical pages in the pool

    @property
    def pages_per_slot(self) -> int:
        return math.ceil(self.max_len / self.page_size)

    @property
    def trash_page(self) -> int:
        """Physical index of the write-off page (block_tab == -1 maps
        here); its contents are never read — every gathered position of an
        unmapped page carries gpos = -1, which decode_attend masks."""
        return self.num_pages

    def pages_for(self, tokens: int) -> int:
        return math.ceil(max(int(tokens), 1) / self.page_size)


def make_layout(max_batch: int, max_len: int, *, page_size: int = 0,
                max_pages: int = 0) -> PageLayout:
    """page_size 0 -> max_len (contiguous degenerate: one page per slot);
    max_pages 0 -> the worst case max_batch * pages_per_slot."""
    ps = page_size or max_len
    if not 1 <= ps <= max_len:
        raise ValueError(f"page_size {ps} outside [1, max_len={max_len}]")
    pps = math.ceil(max_len / ps)
    np_total = max_pages or max_batch * pps
    if np_total < pps:
        raise ValueError(
            f"max_pages={np_total} cannot hold one worst-case request "
            f"({pps} pages of {ps} tokens for max_len={max_len}); the "
            f"Scheduler could never admit it")
    return PageLayout(max_batch, max_len, ps, np_total)


def paged_struct(cfg, layout: PageLayout, *, dtype=jnp.bfloat16):
    """(shapes, specs) for the paged tree: the contiguous struct with
    kv_full re-homed to the page pool plus the block table. The pool is
    stage-sharded exactly like the contiguous group; the block table is
    replicated (every stage resolves the same logical -> physical map)."""
    from repro.models.lm import layer_meta
    shapes, specs = cache_struct(cfg, layout.max_batch, layout.max_len,
                                 dtype=dtype)
    meta = layer_meta(cfg)
    if "kv_full" in shapes:
        st = cfg.stages
        kv_tp = T_AX if (cfg.num_kv_heads and cfg.tp > 1
                         and cfg.num_kv_heads % cfg.tp == 0) else None
        shp = (st * meta["m_full"], layout.num_pages + 1, cfg.num_kv_heads,
               layout.page_size, cfg.head_dim)
        shapes["kv_full"] = tuple(jax.ShapeDtypeStruct(shp, dtype)
                                  for _ in range(2))
        specs["kv_full"] = tuple(P(S_AX, None, kv_tp, None, None)
                                 for _ in range(2))
    shapes["block_tab"] = jax.ShapeDtypeStruct(
        (layout.max_batch, layout.pages_per_slot), jnp.int32)
    specs["block_tab"] = P(None, None)
    return shapes, specs


def init_paged(cfg, layout: PageLayout, *, dtype=jnp.bfloat16):
    shapes, _ = paged_struct(cfg, layout, dtype=dtype)
    tree = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    tree["block_tab"] = jnp.full(shapes["block_tab"].shape, -1, jnp.int32)
    return tree


def is_paged(cache) -> bool:
    return cache is not None and "block_tab" in cache


# ----------------------------------------------------------------------------
# traced page ops (called from models.blocks inside jit / the pipeline scan)
# ----------------------------------------------------------------------------
def _phys(tab, trash):
    return jnp.where(tab >= 0, tab, trash)


def page_view(pool, i, tab):
    """Gather one group's per-row contiguous view through the block table.

    pool [m, NP+1, KV, ps, hd]; tab [B, pps]. Returns (view [B, pps*ps,
    KV, hd], gpos [B, pps*ps]) where gpos is the global position of each
    gathered slot, -1 for unmapped pages (decode_attend masks those)."""
    B, pps = tab.shape
    KV, ps, hd = pool.shape[2:]
    grp = pool[i]                                       # [NP+1, KV, ps, hd]
    view = grp[_phys(tab, pool.shape[1] - 1)]           # [B, pps, KV, ps, hd]
    view = view.transpose(0, 1, 3, 2, 4).reshape(B, pps * ps, KV, hd)
    gpos = jnp.arange(pps * ps, dtype=jnp.int32)[None, :]
    gpos = jnp.where(jnp.repeat(tab >= 0, ps, axis=1), gpos, -1)
    return view, gpos


def page_write_token(pool, i, tab, pos, new_row, sel):
    """Decode-time single-token write: row b's token lands in the page
    holding logical position pos[b]. pool [m, NP+1, KV, ps, hd]; tab
    [B, pps]; pos, sel [B]; new_row [B, 1, KV, hd]. Rows with sel False or
    an unmapped page write to the trash page instead (never read).

    One dynamic_update_slice per row, not a scatter: a scatter of the
    [KV, hd] window (dims 2 and 4, not adjacent) gets a layout of its own
    from XLA, and the paged kernel's operand then relayouts the whole pool
    back at every layer. Row writes keep the pool's layout, so a donated
    pool is updated in place."""
    B, pps = tab.shape
    ps = pool.shape[3]
    trash = pool.shape[1] - 1
    lp = jnp.clip(pos // ps, 0, pps - 1)
    off = (jnp.clip(pos, 0, None) % ps).astype(jnp.int32)
    phys = tab[jnp.arange(B), lp]                       # [B]
    phys = jnp.where(sel & (phys >= 0), phys, trash).astype(jnp.int32)
    rows = new_row[:, 0, :, None, :].astype(pool.dtype)  # [B, KV, 1, hd]
    i, zero = jnp.asarray(i, jnp.int32), jnp.int32(0)
    for b in range(B):
        pool = jax.lax.dynamic_update_slice(
            pool, rows[b][None, None], (i, phys[b], zero, off[b], zero))
    return pool


def page_write_prompt(pool, i, tab, new_kv, sel, lens=None):
    """Prefill-time page-granular scatter of a whole prompt. new_kv
    [B, S, KV, hd] (positions 0..S-1); sel [B] or scalar; lens [B] or None
    (positions >= lens[b] keep the page's previous contents — variable-
    length prompts write only their real tokens). Rows with sel False or
    unmapped pages scatter into the trash page."""
    B, S = new_kv.shape[:2]
    ps = pool.shape[3]
    trash = pool.shape[1] - 1
    pp_in = math.ceil(S / ps)
    pad = pp_in * ps - S
    kv = jnp.pad(new_kv, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else new_kv
    kv = kv.reshape(B, pp_in, ps, *new_kv.shape[2:]).transpose(0, 1, 3, 2, 4)
    tabp = tab[:, :pp_in]
    sel_b = jnp.broadcast_to(jnp.asarray(sel), (B,))
    phys = jnp.where(sel_b[:, None] & (tabp >= 0), tabp, trash)  # [B, pp_in]
    gpos = jnp.arange(pp_in * ps).reshape(pp_in, ps)             # [pp_in, ps]
    live = (gpos[None] < S) if lens is None else \
        (gpos[None] < jnp.minimum(lens, S)[:, None, None])       # [B,pp,ps]
    old = pool[i][phys]                                 # [B, pp_in, KV, ps, hd]
    upd = jnp.where(live[:, :, None, :, None], kv.astype(pool.dtype), old)
    return pool.at[i, phys].set(upd)


# ----------------------------------------------------------------------------
# contiguous single-position writes (the reference implementation the paged
# scatter is parity-tested against; used by the aligned generate() path)
# ----------------------------------------------------------------------------
def upd_kv(group, i, pos_idx, new_row, sel):
    """Single-position conditional cache write: group [m, B, S, KV, hd],
    new_row [B, 1, KV, hd]. Touches only the written row (in-place on TPU)."""
    start = (i, 0, pos_idx, 0, 0)
    old = jax.lax.dynamic_slice(group, start, (1,) + new_row.shape)
    upd = jnp.where(sel, new_row.astype(group.dtype)[None], old)
    return jax.lax.dynamic_update_slice(group, upd, start)


def upd_kv_rows(group, i, pos_idx, new_row, sel):
    """Per-row conditional cache write for continuous batching: each batch
    row b lands at its own position pos_idx[b]. group [m, B, S, KV, hd],
    new_row [B, 1, KV, hd], pos_idx/sel [B]."""
    rows = jnp.arange(group.shape[1])
    old = group[i, rows, pos_idx]                       # [B, KV, hd]
    upd = jnp.where(sel[:, None, None],
                    new_row[:, 0].astype(group.dtype), old)
    return group.at[i, rows, pos_idx].set(upd)


# ----------------------------------------------------------------------------
# pipeline microbatch views (batch at dim 1 of per-slot leaves; the paged
# pool and the block table are shared across microbatches)
# ----------------------------------------------------------------------------
def slice_mb(cache, j, mb):
    """The per-microbatch cache view the pipeline stage computes on: per-
    slot leaves sliced to rows [j*mb, (j+1)*mb); the page pool passes
    through whole (microbatches own disjoint pages, writes are scatters);
    the block table is row-sliced alongside the batch."""
    if cache is None:
        return None
    paged = is_paged(cache)
    out = {}
    for key, v in cache.items():
        if key == "block_tab":
            out[key] = jax.lax.dynamic_slice_in_dim(v, j * mb, mb, axis=0)
        elif paged and key == "kv_full":
            out[key] = v
        else:
            out[key] = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, j * mb, mb, axis=1),
                v)
    return out


def update_mb(cache, new_rows, j, mb, valid):
    """Write a stage's per-microbatch cache updates back: per-slot leaves
    via dynamic_update (masked by tick validity), the page pool wholesale
    (its scatters already routed dead rows to the trash page, and the
    caller only runs this on live ticks), the block table untouched (it is
    read-only inside the step)."""
    paged = is_paged(cache)
    out = {}
    for key, v in cache.items():
        if key == "block_tab":
            out[key] = v
        elif paged and key == "kv_full":
            out[key] = new_rows[key]
        else:
            def upd(a, n):
                old = jax.lax.dynamic_slice_in_dim(a, j * mb, mb, axis=1)
                n = jnp.where(valid, n.astype(a.dtype), old)
                return jax.lax.dynamic_update_slice_in_dim(a, n, j * mb,
                                                           axis=1)
            out[key] = jax.tree.map(upd, v, new_rows[key])
    return out


# ----------------------------------------------------------------------------
# the store
# ----------------------------------------------------------------------------
class CacheStore:
    """Owns one serve cache: the device tree plus host-side page
    accounting. The Scheduler allocates pages at admission and frees them
    at retirement; the Engine's serve steps read/write the tree.

    shardings: optional pytree of NamedShardings matching the tree (spmd
    placement); None keeps plain host-backed arrays (threads backend)."""

    def __init__(self, cfg, layout: PageLayout, *, dtype=jnp.bfloat16,
                 shardings=None):
        self.cfg, self.layout, self.dtype = cfg, layout, dtype
        self._shardings = shardings
        tree = init_paged(cfg, layout, dtype=dtype)
        if shardings is not None:
            tree = jax.device_put(tree, shardings)
        self.tree = tree
        # attention-free / all-windowed families have no full-attention KV
        # group: their decoding state is fixed-size per slot, so there is
        # no pool to ration — alloc/free degrade to slot bookkeeping and
        # can_alloc never blocks admission on phantom pages
        self._has_pool = "kv_full" in tree
        self._tab = np.full((layout.max_batch, layout.pages_per_slot), -1,
                            np.int32)
        self._free = list(range(layout.num_pages)) if self._has_pool else []
        self._owned: dict[int, list[int]] = {}
        self.peak_pages = 0
        # refcounted sharing (repro.serve.memory drives the policy):
        # _ref[p] counts block-table mappings of page p; _retained marks
        # pages the prefix index holds resident at refcount zero (cold —
        # evictable, not free); last_touch is the LRU stamp the eviction
        # policy orders cold pages by; cow_copies counts copy-on-write
        # page duplications taken
        self._ref = np.zeros(layout.num_pages, np.int32)
        self._retained: set[int] = set()
        self.last_touch = np.zeros(layout.num_pages, np.int64)
        self.cow_copies = 0

    # ---- accounting --------------------------------------------------
    @property
    def pages_total(self) -> int:
        return self.layout.num_pages if self._has_pool else 0

    @property
    def pages_in_use(self) -> int:
        """Distinct physical pages not on the free list (mapped by at
        least one slot, or held cold by the prefix index). A page shared
        across N block tables counts once — it is one page of HBM."""
        return self.pages_total - len(self._free)

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_cold(self) -> int:
        """Resident pages no slot maps: index-retained, evictable."""
        return sum(1 for p in self._retained if self._ref[p] == 0)

    def can_alloc(self, tokens: int, shared: int = 0) -> bool:
        """Admission gate: `shared` pages of the request come mapped from
        the prefix index, only the remainder draws on the free list."""
        if not self._has_pool:
            return True
        return len(self._free) >= self.layout.pages_for(tokens) - shared

    def alloc(self, slot: int, tokens: int, shared=()) -> None:
        """Map pages for `tokens` logical positions onto `slot`. The
        leading `shared` pages are already-resident prefix pages
        (refcounts bumped, nothing drawn from the free list); the
        remainder comes fresh from the pool. Raises when the pool is
        exhausted — the Scheduler gates admission on can_alloc() instead
        of over-reserving."""
        lo = self.layout
        if slot in self._owned:
            raise ValueError(f"slot {slot} already holds pages; free() it "
                             f"before re-allocating")
        if tokens > lo.max_len:
            raise ValueError(f"{tokens} tokens exceed max_len={lo.max_len}")
        if not self._has_pool:
            if shared:
                raise ValueError("shared prefix pages need a kv_full pool; "
                                 "this family's state is per-slot only")
            self._owned[slot] = []
            return
        shared = list(shared)
        need = lo.pages_for(tokens)
        if len(shared) > need:
            raise ValueError(f"{len(shared)} shared pages exceed the "
                             f"{need} pages {tokens} tokens need")
        for p in shared:
            if self._ref[p] == 0 and p not in self._retained:
                raise ValueError(f"shared page {p} is not resident (free "
                                 f"list); the prefix index is stale")
        fresh_n = need - len(shared)
        if fresh_n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: need {fresh_n} fresh pages for "
                f"{tokens} tokens ({len(shared)} shared), "
                f"{len(self._free)}/{lo.num_pages} free")
        fresh = self._free[:fresh_n]
        del self._free[:fresh_n]
        pages = shared + fresh
        for p in pages:
            self._ref[p] += 1
        self._owned[slot] = pages
        self._tab[slot, :] = -1
        self._tab[slot, :need] = pages
        self.peak_pages = max(self.peak_pages, self.pages_in_use)
        self._sync_tab()

    def free(self, slot: int) -> list:
        """Unmap `slot`'s pages. Each page's refcount drops; pages
        reaching zero return to the free list unless the prefix index
        retains them — those go *cold* (resident, evictable) and are
        returned so the caller can stamp their LRU clock."""
        pages = self._owned.pop(slot, None)
        if not pages:
            return []
        cold = []
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                if p in self._retained:
                    cold.append(p)
                else:
                    self._free.append(p)
        self._free.sort()
        self._tab[slot, :] = -1
        self._sync_tab()
        return cold

    # ---- sharing / eviction mechanics (policy in repro.serve.memory) --
    def retain(self, page: int) -> None:
        """Prefix-index hold: keep `page` resident when its last slot
        mapping drops (cold, evictable — not free)."""
        self._retained.add(page)

    def release(self, page: int) -> bool:
        """Drop the index hold on `page` (eviction). Returns True when
        the page went back to the free list — i.e. no slot still maps
        it; a mapped page frees later, on its last unmap."""
        self._retained.discard(page)
        if self._ref[page] == 0 and page not in self._free:
            self._free.append(page)
            self._free.sort()
            return True
        return False

    def copy_page(self, src: int, dst: int) -> None:
        """Copy-on-write primitive: device-copy page `src` -> `dst`
        across the K and V pools of every layer group. The writer maps
        the copy; the shared original stays immutable."""
        k, v = self.tree["kv_full"]
        self.tree["kv_full"] = (k.at[:, dst].set(k[:, src]),
                                v.at[:, dst].set(v[:, src]))
        self.cow_copies += 1

    def touch(self, pages, step: int) -> None:
        """Stamp pages' last_touch with the current decode step — the
        LRU clock eviction orders cold pages by."""
        for p in pages:
            self.last_touch[p] = step

    def _sync_tab(self) -> None:
        tab = jnp.asarray(self._tab)
        if self._shardings is not None:
            tab = jax.device_put(tab, self._shardings["block_tab"])
        self.tree["block_tab"] = tab

    # ---- views / updates ---------------------------------------------
    def prefill_input(self, slots, skip_pages=None):
        """The cache tree a prefill step writes into: the live page pool,
        a block table whose row j maps to slots[j]'s pages (-1 rows for
        unused prefill rows), and fresh zeroed per-slot state (computed
        into prefill rows, then adopted via append_rows).

        skip_pages[j] masks row j's first N page entries to -1 *in this
        prefill view only*: those pages hold a shared, already-written
        prefix, so the row's recomputed K/V for them routes to the trash
        page instead of rewriting shared state. The store's real block
        table keeps the mapping — decode reads the shared pages."""
        lo = self.layout
        tab = np.full((lo.max_batch, lo.pages_per_slot), -1, np.int32)
        for j, s in enumerate(slots):
            tab[j] = self._tab[s]
            if skip_pages is not None and skip_pages[j]:
                tab[j, :skip_pages[j]] = -1
        fresh = init_paged(self.cfg, self.layout, dtype=self.dtype)
        fresh["block_tab"] = jnp.asarray(tab)
        if "kv_full" in self.tree:
            fresh["kv_full"] = self.tree["kv_full"]
        if self._shardings is not None:
            fresh = jax.device_put(fresh, self._shardings)
        return fresh

    def append_rows(self, out_tree, pairs) -> None:
        """Absorb a prefill step's output: the page pool is taken
        wholesale (its scatters landed in the admitted slots' pages);
        per-slot leaves are row-copied src -> dst for each (src, dst) in
        pairs — whole-row replacement also clears any stale ring/SSM
        state from a slot's previous occupant."""
        if "kv_full" in self.tree:
            self.tree["kv_full"] = out_tree["kv_full"]
        if not pairs:
            return
        srcs = np.array([s for s, _ in pairs])
        dsts = np.array([d for _, d in pairs])
        for key in SLOT_KEYS:
            if key in self.tree:
                self.tree[key] = jax.tree.map(
                    lambda big, f: big.at[:, dsts].set(f[:, srcs]),
                    self.tree[key], out_tree[key])

    def update(self, out_tree) -> None:
        """Absorb a decode step's full output tree (block table is
        authoritative on the host side and kept as-is)."""
        tab = self.tree["block_tab"]
        self.tree = dict(out_tree)
        self.tree["block_tab"] = tab

    def gather_view(self, group_i: int = 0):
        """Host-side per-row contiguous view of one kv_full group (debug /
        tests): (k [B, pps*ps, KV, hd], v, gpos [B, pps*ps])."""
        k, v = self.tree["kv_full"]
        tab = jnp.asarray(self._tab)
        kv_view, gpos = page_view(k, group_i, tab)
        vv_view, _ = page_view(v, group_i, tab)
        return kv_view, vv_view, gpos

    # ---- reporting ---------------------------------------------------
    def stats(self) -> dict:
        """Page accounting + bytes: the per-stage HBM truth the partitioner
        and the ServeReport read."""
        lo = self.layout
        page_bytes = 0
        if "kv_full" in self.tree:
            k, _ = self.tree["kv_full"]
            # one page across both K and V pools, all layer groups
            page_bytes = 2 * k.shape[0] * int(
                np.prod(k.shape[2:])) * k.dtype.itemsize
        slot_bytes = 0
        for key in SLOT_KEYS:
            if key in self.tree:
                slot_bytes += sum(int(l.nbytes) for l in
                                  jax.tree.leaves(self.tree[key]))
        return {
            "page_size": lo.page_size,
            "pages_total": self.pages_total,
            "pages_in_use": self.pages_in_use,
            "pages_free": len(self._free),
            "pages_cold": self.pages_cold,
            "pages_shared": int((self._ref > 1).sum()) if self._has_pool
            else 0,
            "cow_copies": self.cow_copies,
            "peak_pages": self.peak_pages,
            "page_bytes": page_bytes,
            "pool_bytes": page_bytes * self.pages_total,
            "slot_state_bytes": slot_bytes,
            "utilization": (self.pages_in_use / self.pages_total
                            if self.pages_total else 0.0),
        }
