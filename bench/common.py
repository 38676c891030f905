"""What every part of the benchmark shares: where the files are, how a cell
is looked up by name, seeds, weights made from a seed, the device record,
and the count of compilations.

Nothing here imports the program at module level; `program()` puts the
checkout's `src/` on the path when a driver needs it.
"""
from __future__ import annotations

import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    return load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))


def cell_files(workload: str, spec: dict) -> tuple[dict, dict, dict, dict]:
    """(workload entry, configuration, traffic mix, cell sizing) of one cell,
    each read from the file its name points at."""
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[workload]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    cfg = load_json(os.path.join(CHECKOUT, conf["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    cell_path = os.path.join(BENCH, "cells", workload + ".json")
    cell = load_json(cell_path) if os.path.exists(cell_path) else {}
    return w, cfg, traffic, cell


def program():
    """Make the checkout's program importable (`repro`)."""
    src = os.path.join(CHECKOUT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise ImportError(f"the program is not in this checkout ({src})")
    if src not in sys.path:
        sys.path.insert(0, src)


ARCH_KEYS = (("hidden_size", "d_model"), ("intermediate_size", "d_ff"),
             ("num_hidden_layers", "num_layers"),
             ("num_attention_heads", "num_heads"),
             ("num_key_value_heads", "num_kv_heads"),
             ("head_dim", "head_dim"), ("vocab_size", "vocab_size"),
             ("rope_theta", "rope_theta"), ("rms_norm_eps", "norm_eps"),
             ("tie_word_embeddings", "tie_embeddings"), ("qk_norm", "qk_norm"))


def arch_for(cfg: dict):
    """The program's ArchConfig for `cfg`, checked against every size the
    configuration file states: a program that no longer runs this
    configuration is refused, not measured."""
    from repro.configs import ARCHS
    arch = ARCHS[cfg["arch"]]
    bad = [(k, cfg[k], getattr(arch, a)) for k, a in ARCH_KEYS
           if cfg[k] != getattr(arch, a)]
    window = arch.window_size if arch.attn_type in ("swa",) else 0
    if (cfg.get("sliding_window") or 0) != window:
        bad.append(("sliding_window", cfg.get("sliding_window"), window))
    if cfg["hidden_act"] != "silu" or arch.mlp_type != "swiglu":
        bad.append(("hidden_act", cfg["hidden_act"], arch.mlp_type))
    if bad:
        raise ValueError(f"{cfg['name']}: the program's {arch.name} differs "
                         f"from the configuration file: {bad}")
    return arch


def seed_key(seed: int):
    """A PRNG key that keeps all 64 bits of the seed (PRNGKey alone drops
    the bits above 32)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def np_rng(seed: int, *stream: int):
    import numpy as np
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
NORM_LEAVES = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")
OUT_LEAVES = ("wo", "mlp_wo")


def make_params(shapes: dict, num_layers: int, seed: int):
    """Weights from the seed, on the device, in one jitted call, laid out as
    the program's parameter tree (`shapes`: the tree of ShapeDtypeStructs
    the program declares). Norm leaves hold the offset from a gain of 1
    (the program scales by 1 + w) and are drawn around 0, so that a norm
    that ignores its gain is caught; projections are N(0, 0.02), output
    projections N(0, 0.02 / sqrt(2 L))."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree.flatten_with_path(shapes)
    names = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in flat]

    def scale(name):
        leaf = name.split("/")[-1]
        if leaf in NORM_LEAVES:
            return 0.1
        if leaf in OUT_LEAVES:
            return 0.02 / math.sqrt(2 * num_layers)
        return 0.02

    def build(key):
        keys = jax.random.split(key, len(flat))
        out = [(scale(n) * jax.random.normal(k, s.shape, jnp.float32))
               .astype(s.dtype) for n, k, (_, s) in zip(names, keys, flat)]
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------------------
# the device and the compilations
# ---------------------------------------------------------------------------
def device_record(devices) -> dict:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


class CompileCounter:
    """Counts XLA backend compilations and persistent-cache hits through
    jax.monitoring, so that a run can show that its window compiled
    nothing and that a warm run loaded its programs from the cache."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def start_trace(log_dir: str):
    """Start the JAX profiler with device and host-annotation events and
    without Python call events (those swamp a trace of a few seconds)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def quantile(values, q: float) -> float:
    """The q-quantile by linear interpolation between order statistics."""
    v = sorted(values)
    if not v:
        return float("nan")
    x = q * (len(v) - 1)
    lo = int(math.floor(x))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (x - lo)
