"""Operations and bytes the algorithms need, from shapes alone: per kernel
call and per model token. The per-kernel counts follow
benchmarks/kernels_bench.py (flash_attention: causal pairs; flash_decode /
flash_decode_paged: the live positions of each row), counted per row so
that ragged batches come out exact, with the operand width as a parameter
(the program serves f32).
"""
from __future__ import annotations

import json
import os


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device that is not in the table is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]


def flash_attention(lens, H: int, KV: int, hd: int, width: int,
                    window: int = 0) -> tuple[float, float]:
    """Causal attention over right-padded prompts: (flops, bytes) of the
    real rows (`lens`, one per live row), one layer."""
    flops = bytes_ = 0.0
    for s in lens:
        s = int(s)
        w = window if window and window < s else 0
        live = s * w - w * (w - 1) // 2 if w else s * (s + 1) // 2
        flops += 4.0 * H * hd * live                       # qk + pv
        bytes_ += width * (2 * H * s * hd + 2 * KV * s * hd)   # q, o, k, v
    return flops, bytes_


def flash_decode_paged(lens, H: int, KV: int, hd: int,
                       width: int) -> tuple[float, float]:
    """One query token per live row against its `lens` cached positions,
    one layer: (flops, bytes); bytes are the live keys and values plus the
    query and output rows and each row's block-table entries."""
    flops = bytes_ = 0.0
    for n in lens:
        n = int(n)
        flops += 4.0 * H * hd * n
        bytes_ += width * (2 * KV * n * hd + 2 * H * hd) + 4
    return flops, bytes_


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product per token (every
    projection and the output head; not the embedding lookup, not norms)."""
    d, ff, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    per_layer = d * H * hd * 2 + d * KV * hd * 2 + 3 * d * ff
    return L * per_layer + d * cfg["vocab_size"]


def attention_flops(cfg: dict, context: int) -> float:
    """Forward attention flops of one token that attends to `context`
    positions, over all layers (qk + pv)."""
    H = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    w = cfg.get("sliding_window") or 0
    ctx = min(context, w) if w else context
    return 4.0 * cfg["num_hidden_layers"] * H * hd * ctx


def forward_flops(cfg: dict, context: int) -> float:
    """Forward flops of one token at position `context - 1`."""
    return 2.0 * matmul_params(cfg) + attention_flops(cfg, context)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward flops per trained token of sequences of `seq`
    (6 x matmul parameters, plus 3 x the causal attention's forward at the
    mean context); recomputation is not counted."""
    mean_ctx = (seq + 1) / 2
    return 6.0 * matmul_params(cfg) + 3.0 * attention_flops(cfg, mean_ctx)
