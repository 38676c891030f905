"""Ahead-of-time compiles of the serve-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot: a
block shape Mosaic refuses, an unsupported primitive, a kernel that asks
for more VMEM than a core has. Shapes are the published widths of the
configs the kernels serve. The paged decode step is compiled whole too, to
check from its HLO that it updates the page pool in place. Nothing runs,
so nothing here says anything about results or speed.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.mamba_ssd import ssd_chunked
from repro.kernels.rwkv6_scan import rwkv6_chunked

QWEN = ARCHS["qwen3-0.6b"]
DANUBE = ARCHS["h2o-danube-1.8b"]
RWKV = ARCHS["rwkv6-3b"]
HYMBA = ARCHS["hymba-1.5b"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the Mosaic kernel is not in the HLO"


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("S", [512, 200])   # 200: padded to a block multiple
@pytest.mark.parametrize("dtype", [F32, BF16])
def test_flash_attention_full_qwen3(one_chip, S, dtype):
    H, KV, hd = QWEN.num_heads, QWEN.num_kv_heads, QWEN.head_dim
    _compile(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True),
             one_chip, ((1, H, S, hd), dtype), ((1, KV, S, hd), dtype),
             ((1, KV, S, hd), dtype))


def test_flash_attention_window_danube(one_chip):
    H, KV, hd = DANUBE.num_heads, DANUBE.num_kv_heads, DANUBE.head_dim
    S = 512
    _compile(lambda q, k, v: flash_attention_fwd(
        q, k, v, causal=True, window=DANUBE.window_size), one_chip,
        ((1, H, S, hd), F32), ((1, KV, S, hd), F32), ((1, KV, S, hd), F32))


@pytest.mark.parametrize("S", [2048, 544])  # 544: padded to a block multiple
def test_flash_decode_qwen3(one_chip, S):
    H, KV, hd = QWEN.num_heads, QWEN.num_kv_heads, QWEN.head_dim
    B = 8
    _compile(lambda q, k, v, n: flash_decode(q, k, v, n), one_chip,
             ((B, H, hd), F32), ((B, KV, S, hd), F32), ((B, KV, S, hd), F32),
             ((B,), I32))


@pytest.mark.parametrize("ps,dtype", [
    (128, F32), (16, F32), (128, BF16), (16, BF16),
    (128, jnp.float8_e4m3fn),
    (544, F32),           # page_size = max_len: head blocks split for VMEM
    (4096, F32),          # one head of a page over budget: sub-page blocks
])
def test_flash_decode_paged_qwen3(one_chip, ps, dtype):
    """The Scheduler's decode kernel at qwen3-0.6b's widths (28 layer
    groups, 8 KV heads of 128) over a pool for 8 slots of 544 positions."""
    H, KV, hd = QWEN.num_heads, QWEN.num_kv_heads, QWEN.head_dim
    B, max_len = 8, 544
    npg = -(-max_len // ps)
    pool = ((QWEN.num_layers, B * npg + 1, KV, ps, hd), dtype)
    _compile(lambda q, k, v, t, n: flash_decode_paged(q, k, v, t, n,
                                                      layer=3),
             one_chip, ((B, H, hd), F32), pool, pool, ((B, npg), I32),
             ((B,), I32))


@pytest.mark.parametrize("S", [512, 200])   # 200: padded to a chunk multiple
def test_rwkv6_chunked_rwkv6_3b(one_chip, S):
    H = RWKV.n_ssm_heads
    hd = RWKV.d_model // H
    seq = ((1, H, S, hd), F32)
    _compile(rwkv6_chunked, one_chip, seq, seq, seq, seq, ((H, hd), F32))


@pytest.mark.parametrize("S", [512, 200])   # 200: padded to a chunk multiple
def test_ssd_chunked_hymba(one_chip, S):
    H, N = HYMBA.n_ssm_heads, HYMBA.ssm_state
    P = HYMBA.d_inner // H
    _compile(ssd_chunked, one_chip, ((1, H, S, P), F32), ((1, H, S), F32),
             ((1, S, N), F32), ((1, S, N), F32), ((H,), F32))


def test_paged_decode_step_updates_pool_in_place(one_chip):
    """The paged decode step, jitted as the Engine jits it, at qwen3-0.6b's
    widths cut to 2 layers (16 slots, max_len 2560, 80 pages of 128, f32):
    no copy produces a pool-shaped array, both donated pools alias outputs,
    and the step's one Mosaic kernel per layer keeps the step's name."""
    from repro.api.engine import _PoolDonatingDecode, _ref_paged_steps
    from repro.models import lm
    from repro.serve import cache as cache_lib
    cfg = dataclasses.replace(QWEN, num_layers=2)
    B = 16
    layout = cache_lib.make_layout(B, 2560, page_size=128, max_pages=80)
    tree, _ = cache_lib.paged_struct(cfg, layout, dtype=F32)
    sds = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    tree = jax.tree.map(sds, tree)
    params = jax.tree.map(sds, lm.param_shapes(cfg))
    _, dec_fn = _ref_paged_steps(cfg, "tpu")
    compiled = _PoolDonatingDecode(dec_fn).lower(
        params, sds(jax.ShapeDtypeStruct((B, 1), I32)), tree,
        sds(jax.ShapeDtypeStruct((B,), I32))).compile()
    text = compiled.as_text()
    pool = tree["kv_full"][0]
    shape = "f32[" + ",".join(map(str, pool.shape)) + "]"
    copies = re.findall(r"= " + re.escape(shape) + r"\{[^}]*\} copy\(",
                        text)
    assert not copies, f"{len(copies)} pool-sized copies in the decode step"
    pool_bytes = pool.size * pool.dtype.itemsize
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * pool_bytes
    kernels = re.findall(r"%(\S+) = .*custom-call\(.*tpu_custom_call", text)
    assert len(kernels) == cfg.num_layers
    assert all(k.startswith("dec_fn") for k in kernels), kernels
