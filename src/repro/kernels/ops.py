"""jit'd wrappers over the Pallas kernels with a backend switch.

KERNEL_BACKEND:
  "ref"       — pure-jnp oracles (default on CPU / in the dry-run: Mosaic
                cannot lower for the CPU backend)
  "interpret" — pallas_call(interpret=True): the kernel body executed in
                Python — used by the correctness sweeps in tests/
  "tpu"       — compiled Mosaic kernels (the deployment target)

Layout adapters between the model convention ([B, S, H, hd]) and the kernel
convention ([B, H, S, hd]) live here.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.mamba_ssd import ssd_chunked
from repro.kernels.moe_gmm import grouped_matmul
from repro.kernels.rwkv6_scan import rwkv6_chunked

BACKENDS = ("ref", "interpret", "tpu")

KERNEL_BACKEND = "ref"


def check_backend(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {name!r}: expected one of {BACKENDS}")
    return name


def set_backend(name: str):
    global KERNEL_BACKEND
    KERNEL_BACKEND = check_backend(name)


@contextlib.contextmanager
def use_backend(name: str):
    """Scoped backend switch — restores the previous backend on exit, so
    parity tests cannot leak a process-global setting into each other."""
    global KERNEL_BACKEND
    prev = KERNEL_BACKEND
    set_backend(name)
    try:
        yield
    finally:
        KERNEL_BACKEND = prev


def _interp():
    return KERNEL_BACKEND == "interpret"


def attention(q, k, v, *, causal=True, window=0, backend=None):
    """Model layout: q [B,S,H,hd], k/v [B,S,KV,hd] -> [B,S,H,hd]."""
    be = backend or KERNEL_BACKEND
    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    if be == "ref":
        o = kref.attention_ref(qT, kT, vT, causal=causal, window=window)
    else:
        o = flash_attention_fwd(qT, kT, vT, causal=causal, window=window,
                                interpret=(be == "interpret"))
    return o.transpose(0, 2, 1, 3)


def decode_attention(q1, k, v, length, *, window=0, backend=None):
    """q1 [B,H,hd]; k/v [B,KV,S,hd] kernel-native; length scalar or [B]."""
    be = backend or KERNEL_BACKEND
    if be == "ref":
        return kref.decode_ref(q1, k, v, length, window=window)
    return flash_decode(q1, k, v, length, window=window,
                        interpret=(be == "interpret"))


def decode_attention_paged(q1, k_pool, v_pool, block_tab, lengths, *,
                           layer=0, backend=None):
    """Fused paged decode: pools [groups, num_pages+1, KV, page_size, hd]
    walked through block_tab [B, pages_per_slot] with per-row lengths.
    The "ref" backend gathers the paged view first (the materialization the
    kernel backends avoid)."""
    be = backend or KERNEL_BACKEND
    if be == "ref":
        return kref.decode_paged_ref(q1, k_pool, v_pool, block_tab, lengths,
                                     layer=layer)
    return flash_decode_paged(q1, k_pool, v_pool, block_tab, lengths,
                              layer=layer, interpret=(be == "interpret"))


def rwkv6(r, k, v, w, u, *, backend=None):
    be = backend or KERNEL_BACKEND
    if be == "ref":
        return kref.rwkv6_ref(r, k, v, w, u)
    return rwkv6_chunked(r, k, v, w, u, interpret=(be == "interpret"))


def ssd(x, dt, B_, C_, a, *, backend=None):
    be = backend or KERNEL_BACKEND
    if be == "ref":
        return kref.ssd_ref(x, dt, B_, C_, a)
    return ssd_chunked(x, dt, B_, C_, a, interpret=(be == "interpret"))


def gmm(x, w, *, backend=None):
    be = backend or KERNEL_BACKEND
    if be == "ref":
        return kref.gmm_ref(x, w)
    return grouped_matmul(x, w, interpret=(be == "interpret"))
