"""Threaded multi-VW WSP runtime: convergence, stragglers, checkpoint/restart,
elastic fail/rejoin, compression."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.wave import build_local_wave_step
from repro.models import lm
from repro.optim import make_optimizer
from repro.runtime.checkpoint import (save_checkpoint, load_checkpoint,
                                      latest_checkpoint)
from repro.runtime.trainer import WSPTrainer, bsp_allreduce_baseline

CFG = reduced(ARCHS["qwen3-0.6b"], num_layers=2, d_model=32, d_ff=64,
              vocab_size=256, num_heads=2, num_kv_heads=2, head_dim=16,
              num_microbatches=2)


def _setup(lr=0.3):
    params, _ = lm.init_params(CFG, jax.random.PRNGKey(0))
    opt = make_optimizer("sgd", lr)
    step = build_local_wave_step(CFG, CFG.num_microbatches, opt)
    return params, opt, step


def _final_loss(report, last=8):
    xs, ys = report.loss_curve()
    return float(np.mean(ys[-last:]))


def test_local_wave_step_takes_turns_and_returns_host_deltas(monkeypatch):
    """Virtual workers share one device: their wave steps never run at
    once, and the deltas come back on the host, so the device holds one
    wave's buffers at a time."""
    import threading
    import time
    params, opt, step = _setup()
    x = np.zeros((4, 16), np.int32)
    step(params, opt.init(params), x, x)            # compile outside the race
    running, seen = [], []
    device_get = jax.device_get

    def slow_device_get(tree):      # widens the window a second call could use
        running.append(1)
        seen.append(len(running))
        time.sleep(0.05)
        running.pop()
        return device_get(tree)

    monkeypatch.setattr(jax, "device_get", slow_device_get)
    results = []
    ts = [threading.Thread(target=lambda: results.append(
        step(params, opt.init(params), x, x))) for _ in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(results) == 3 and max(seen) == 1
    for deltas, _, loss in results:
        assert all(isinstance(l, np.ndarray) for l in jax.tree.leaves(deltas))
        assert np.isfinite(float(loss))


def test_wsp_trainer_converges():
    params, opt, step = _setup()
    tr = WSPTrainer(params, step, opt, num_vw=2, D=1, batch=8, seq=32,
                    vocab=CFG.vocab_size, max_waves=12)
    rep = tr.run()
    xs, ys = rep.loss_curve()
    assert len(ys) >= 20
    assert _final_loss(rep) < ys[0] - 0.3       # real learning happened
    assert rep.bytes_pushed > 0


def test_wsp_straggler_d_allows_progress():
    """With a slow VW, D=2 lets the fast VW run ahead (its wave count beats
    the slow one's), while D=0 keeps them in lock step."""
    params, opt, step = _setup()
    for D, expect_ahead in ((0, False), (2, True)):
        tr = WSPTrainer(params, step, opt, num_vw=2, D=D, batch=4, seq=32,
                        vocab=CFG.vocab_size, max_waves=8,
                        speeds=[0.0, 0.12])
        tr.run()
        clocks = tr.ps.clock.state.clocks
        gap = abs(clocks["vw0"] - clocks["vw1"])
        if expect_ahead:
            assert tr.ps.clock.wait_seconds["vw0"] < 2.0
        else:
            assert gap <= 1


def test_bsp_baseline_converges():
    params, opt, step = _setup()
    rep = bsp_allreduce_baseline(params, step, opt, num_vw=2, batch=8,
                                 seq=32, vocab=CFG.vocab_size, max_waves=12)
    xs, ys = rep.loss_curve()
    assert _final_loss(rep) < ys[0] - 0.3


def test_elastic_fail_and_rejoin():
    params, opt, step = _setup()
    tr = WSPTrainer(params, step, opt, num_vw=3, D=1, batch=4, seq=32,
                    vocab=CFG.vocab_size, max_waves=8, fail_at={2: 2})
    rep = tr.run(rejoin_failed_after=0.2)
    # survivors finished their waves despite vw2 dying at wave 2
    assert tr.workers["vw2"].failed
    assert tr.ps.clock.state.clocks["vw0"] == 8
    assert tr.ps.clock.state.clocks["vw1"] == 8
    # the re-joined worker registered at the global clock and either made
    # progress or (under CPU contention) joined after the fleet finished —
    # in which case its clock equals the target
    rejoined = [w for k, w in tr.workers.items() if k.endswith("r")]
    assert rejoined
    rj = rejoined[0]
    clock = tr.ps.clock.state.clocks.get(rj.wid)
    assert rj.metrics.waves > 0 or clock == 8, (rj.metrics.waves, clock)


def test_async_push_matches_blocking_runtime():
    """Single-VW determinism: the async-push runtime (outbox thread, clock
    advanced at push-land time) must reproduce the blocking runtime's WSP
    clock trace, loss sequence, and final PS params exactly at
    time_scale=0-equivalent conditions."""
    params, opt, step = _setup()
    reps, trs = {}, {}
    for mode in (False, True):
        tr = WSPTrainer(params, step, opt, num_vw=1, D=1, batch=4, seq=32,
                        vocab=CFG.vocab_size, max_waves=6, pull_every=2,
                        async_push=mode)
        reps[mode] = tr.run()
        trs[mode] = tr
    assert trs[True].ps.clock.state.clocks == trs[False].ps.clock.state.clocks
    assert reps[True].waves == reps[False].waves == 6
    np.testing.assert_array_equal(reps[True].loss_curve()[1],
                                  reps[False].loss_curve()[1])
    for a, b in zip(trs[True].ps.flat, trs[False].ps.flat):
        np.testing.assert_array_equal(a, b)


def test_async_push_multi_vw_converges_and_overlaps():
    """Two async VWs over a simulated heterogeneous network: training still
    converges, every wave lands (clocks reach max_waves), and part of the
    push time is hidden under the next wave's compute."""
    from repro.dist.topology import ClusterTopology, LinkSpec, Pod, NVLINK
    params, opt, step = _setup()
    slow_eth = LinkSpec("slow-eth", 0.05, 0.01)
    topo = ClusterTopology([Pod("node0", ("vw0",), NVLINK),
                            Pod("node1", ("vw1",), NVLINK)], inter=slow_eth)
    tr = WSPTrainer(params, step, opt, num_vw=2, D=2, batch=8, seq=32,
                    vocab=CFG.vocab_size, max_waves=12, pull_every=4,
                    topology=topo, time_scale=1.0, speeds=[0.02, 0.02],
                    async_push=True)
    rep = tr.run()
    assert tr.ps.clock.state.clocks == {"vw0": 12, "vw1": 12}
    assert _final_loss(rep) < rep.loss_curve()[1][0] - 0.3
    assert rep.overlap_seconds > 0.0          # some comm was hidden
    assert rep.comm_seconds > 0.0


def test_async_push_respects_staleness_gate():
    """With D=0 the async runtime degenerates to lock step: neither worker
    may run a wave ahead even though pushes land off-thread — the fast
    worker provably blocks at the gate waiting for the slow one."""
    params, opt, step = _setup()
    tr = WSPTrainer(params, step, opt, num_vw=2, D=0, batch=4, seq=32,
                    vocab=CFG.vocab_size, max_waves=6, async_push=True,
                    speeds=[0.0, 0.05])
    tr.run()
    clocks = tr.ps.clock.state.clocks
    assert clocks == {"vw0": 6, "vw1": 6}
    # vw1 sleeps 0.05 s/wave; under D=0 lock step vw0 must absorb most of
    # that at the gate — if gating were broken vw0 would never wait
    assert tr.ps.clock.wait_seconds["vw0"] > 0.1


def test_compression_error_feedback_converges():
    params, opt, step = _setup(lr=0.3)
    tr = WSPTrainer(params, step, opt, num_vw=2, D=0, batch=8, seq=32,
                    vocab=CFG.vocab_size, max_waves=12,
                    compression_ratio=0.25)
    rep = tr.run()
    xs, ys = rep.loss_curve()
    assert _final_loss(rep) < ys[0] - 0.2
    assert rep.bytes_wire < 0.7 * rep.bytes_pushed   # wire savings real


def test_checkpoint_roundtrip_exact():
    params, _ = lm.init_params(CFG, jax.random.PRNGKey(1))
    opt = make_optimizer("adamw", 1e-3)
    state = opt.init(params)
    with tempfile.TemporaryDirectory() as d:
        path = save_checkpoint(d, 3, {"params": params, "opt": state},
                               {"note": "t"})
        assert latest_checkpoint(d) == path
        out, meta = load_checkpoint(path, {"params": params, "opt": state})
        assert meta["step"] == 3
        for a, b in zip(jax.tree.leaves(out["params"]),
                        jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_checkpoint_restart_continuity():
    """Kill training at wave k, restore PS state, continue — the restored
    PS weights equal the checkpointed ones exactly."""
    params, opt, step = _setup()
    with tempfile.TemporaryDirectory() as d:
        tr = WSPTrainer(params, step, opt, num_vw=2, D=0, batch=4, seq=32,
                        vocab=CFG.vocab_size, max_waves=6,
                        ckpt_dir=d, ckpt_every=2)
        tr.run()
        path = latest_checkpoint(d)
        assert path is not None
        out, meta = load_checkpoint(path, {"params": params})
        tr2 = WSPTrainer(out["params"], step, opt, num_vw=2, D=0, batch=4,
                         seq=32, vocab=CFG.vocab_size, max_waves=2)
        rep2 = tr2.run()
        assert rep2.waves == 4      # 2 workers x 2 waves from the restart
