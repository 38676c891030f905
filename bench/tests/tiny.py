"""Tiny stand-ins of the benchmark's configurations, traffic and cells, for
the CPU tests of the harness and its references. Published families,
shrunk widths: these sizes never stand for a deployment."""
from __future__ import annotations

import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import common  # noqa: E402

common.program()

from repro.configs import ARCHS  # noqa: E402


def tiny_arch(base: str, name: str, **over):
    arch = dataclasses.replace(
        ARCHS[base], name=name, num_layers=2, d_model=64, d_ff=128,
        vocab_size=4096, num_heads=4, num_kv_heads=2, head_dim=16,
        stages=1, tp=1, num_microbatches=4, **over)
    return arch


def cfg_of(arch, kernel_backend="ref") -> dict:
    return {
        "name": arch.name, "arch": arch.name, "source": "test",
        "hidden_size": arch.d_model, "intermediate_size": arch.d_ff,
        "num_hidden_layers": arch.num_layers,
        "num_attention_heads": arch.num_heads,
        "num_key_value_heads": arch.num_kv_heads,
        "head_dim": arch.head_dim, "vocab_size": arch.vocab_size,
        "rope_theta": arch.rope_theta, "rms_norm_eps": arch.norm_eps,
        "tie_word_embeddings": arch.tie_embeddings, "hidden_act": "silu",
        "sliding_window": (arch.window_size if arch.attn_type == "swa"
                           else None),
        "qk_norm": arch.qk_norm, "kernel_backend": kernel_backend,
    }


QWEN = tiny_arch("qwen3-0.6b", "tiny-qwen3")
DANUBE = tiny_arch("h2o-danube-1.8b", "tiny-danube", window_size=48)

SERVE_TRAFFIC = {
    "driver": "serve_backlog", "requests": 512, "block": 16,
    "prompt": {"median": 24, "sigma": 0.6, "min": 4, "max": 48},
    "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
    "prompt_len": 48, "gen": 24, "page_size": 8, "warm_steps": 4,
    "trace_seconds": 1, "check": {"min_tokens": 100000, "max_requests": 64},
}

TRAIN_TRAFFIC = {
    "driver": "train_wsp", "num_vw": 2, "D": 1, "batch": 4, "seq": 32,
    "microbatches": 4, "optimizer": "sgd", "lr": 0.3, "warm_pushes": 4,
    "trace_seconds": 1, "check_steps": 3,
}


def register(monkeypatch):
    """Put the tiny archs in the program's registry, and draw the weights
    4x wider than the published init: at these widths the published init
    leaves each token's own embedding dominating its logits, so the greedy
    token would hide what attention does."""
    import jax
    for arch in (QWEN, DANUBE):
        monkeypatch.setitem(ARCHS, arch.name, arch)
    make = common.make_params

    def wider(shapes, num_layers, seed):
        p = make(shapes, num_layers, seed)
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x if str(path[-1].key) in common.NORM_LEAVES
            else 4.0 * x, p)

    monkeypatch.setattr(common, "make_params", wider)


def spec(workload: str, e2e, layer=()) -> dict:
    return {"workloads": [{"name": workload, "config": "c", "traffic": "t",
                           "chips": 1, "why": "test"}],
            "end_to_end": [{"name": n, "unit": "u", "better": "lower",
                            "bound": 0.1, "source": "host_clock"}
                           for n in e2e],
            "per_layer": list(layer)}
