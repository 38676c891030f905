"""Ahead-of-time compiles of the serve-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot: a
block shape Mosaic refuses, an unsupported primitive, a kernel that asks
for more VMEM than a core has. Shapes are the published widths of the
configs the kernels serve. Nothing runs, so nothing here says anything
about results or speed.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

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
