"""Subprocess body for pipeline-vs-oracle equivalence (needs 8 fake devices,
so it must own the process — XLA device count is locked at first jax import).

Run: XLA_FLAGS=... python tests/pipeline_equiv_main.py <arch> [decode]
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402
import numpy as np                           # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, reduced, RunConfig, ShapeConfig  # noqa: E402
from repro.core import wave                  # noqa: E402
from repro.models import lm                  # noqa: E402
from repro.optim import make_optimizer       # noqa: E402


def main(arch_name: str, mode: str = "train") -> int:
    from repro.launch.mesh import make_mesh_auto
    mesh = make_mesh_auto((2, 2, 2), ("data", "stage", "tp"))
    key = jax.random.PRNGKey(0)
    over = {"capacity_factor": 8.0} if ARCHS[arch_name].num_experts else {}
    cfg = reduced(ARCHS[arch_name], stages=2, tp=2, num_layers=4,
                  num_microbatches=2, remat=True, **over)
    params, pspecs = lm.init_params(cfg, key)

    if mode == "overlap":
        # skewed (comm/compute-overlapped) schedule vs the oracle schedule:
        # one train step each from identical state must agree exactly
        shape = ShapeConfig("tiny", 32, 8, "train")
        B, S = shape.global_batch, shape.seq_len
        if cfg.frontend != "none":
            inputs = 0.02 * jax.random.normal(key, (B, S, cfg.d_model))
        else:
            inputs = jax.random.randint(key, (B, S), 0, cfg.vocab_size,
                                        dtype=jnp.int32)
        labels = jax.random.randint(key, (B, S), 0, cfg.vocab_size,
                                    dtype=jnp.int32)
        opt = make_optimizer("sgd", 0.1)
        results = {}
        for overlap in (False, True):
            run = RunConfig(arch=cfg, shape=shape, optimizer="sgd", lr=0.1,
                            compute_dtype="float32", loss_chunk=16,
                            overlap=overlap)
            step, _ = wave.build_train_step(run, mesh)
            with jax.set_mesh(mesh):
                p_sh = jax.device_put(params, jax.tree.map(
                    lambda s: NamedSharding(mesh, s), pspecs,
                    is_leaf=lambda x: isinstance(x, P)))
                new_p, _, metrics = jax.jit(step)(
                    p_sh, opt.init(params),
                    {"inputs": inputs, "labels": labels})
            results[overlap] = (jax.tree.map(np.asarray, new_p),
                                float(metrics["loss"]))
        ld = abs(results[True][1] - results[False][1])
        md = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(np.max(np.abs(a - b))),
            results[True][0], results[False][0])))
        print(f"overlap_loss_diff={ld:.3e} overlap_param_diff={md:.3e}")
        assert ld == 0.0, ld          # same compute per microbatch, same order
        assert md < 1e-6, md
        return 0

    if mode == "train":
        shape = ShapeConfig("tiny", 32, 8, "train")
        run = RunConfig(arch=cfg, shape=shape, optimizer="sgd", lr=0.1,
                        compute_dtype="float32", loss_chunk=16)
        B, S = shape.global_batch, shape.seq_len
        if cfg.frontend != "none":
            inputs = 0.02 * jax.random.normal(key, (B, S, cfg.d_model))
        else:
            inputs = jax.random.randint(key, (B, S), 0, cfg.vocab_size,
                                        dtype=jnp.int32)
        labels = jax.random.randint(key, (B, S), 0, cfg.vocab_size,
                                    dtype=jnp.int32)
        step, _ = wave.build_train_step(run, mesh)
        opt = make_optimizer("sgd", 0.1)
        with jax.set_mesh(mesh):
            p_sh = jax.device_put(params, jax.tree.map(
                lambda s: NamedSharding(mesh, s), pspecs,
                is_leaf=lambda x: isinstance(x, P)))
            new_p, _, metrics = jax.jit(step)(
                p_sh, opt.init(params), {"inputs": inputs, "labels": labels})
        local = wave.build_local_wave_step(cfg, 4, opt)
        deltas, _, loss_local = local(params, opt.init(params), inputs,
                                      labels)
        p_local = jax.tree.map(jnp.add, params, deltas)
        md = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), new_p, p_local)))
        print(f"max_param_diff={md:.3e}")
        assert md < 1e-4, md  # bf16 CE matmul epsilon
        return 0

    # decode equivalence: pipelined decode_step (both schedules) == reference
    shape = ShapeConfig("tinydec", 32, 16, "decode")
    B, S = shape.global_batch, shape.seq_len
    if cfg.frontend != "none":
        full = 0.02 * jax.random.normal(key, (B, S, cfg.d_model))
    else:
        full = jax.random.randint(key, (B, S), 0, cfg.vocab_size,
                                  dtype=jnp.int32)
    PRE = S - 1
    cache = lm.init_cache(cfg, B, S, dtype=jnp.float32)
    _, cache, _ = lm.forward_ref(cfg, params, full[:, :PRE], mode="prefill",
                                 cache=cache)
    hd_ref, _, _ = lm.forward_ref(
        cfg, params,
        full[:, PRE:], mode="decode",
        cache=jax.tree.map(lambda a: a.copy(), cache), pos=jnp.int32(PRE))
    ref_logits = lm.logits_ref(cfg, params, hd_ref)
    by_sched = {}
    for overlap in (False, True):
        run_o = RunConfig(arch=cfg, shape=shape, compute_dtype="float32",
                          overlap=overlap)
        step, pspecs2, cspecs = wave.build_decode_step(run_o, mesh)
        with jax.set_mesh(mesh):
            p_sh = jax.device_put(params, jax.tree.map(
                lambda s: NamedSharding(mesh, s), pspecs,
                is_leaf=lambda x: isinstance(x, P)))
            logits, _ = jax.jit(step)(p_sh, {
                "inputs": full[:, PRE:],
                "cache": jax.tree.map(lambda a: a.copy(), cache),
                "pos": jnp.int32(PRE)})
        by_sched[overlap] = logits
    md = float(jnp.max(jnp.abs(by_sched[False] - ref_logits)))
    od = float(jnp.max(jnp.abs(by_sched[True] - by_sched[False])))
    print(f"decode_logits_diff={md:.3e} decode_overlap_diff={od:.3e}")
    assert md < 1e-3, md
    assert od == 0.0, od   # skewed serve schedule identical to the oracle
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else "train"))
