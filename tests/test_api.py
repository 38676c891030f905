"""The repro.api experiment layer: Plan validation, SyncPolicy dispatch,
Engine parity with the legacy constructors, presets, checkpoint atomicity
under async push, and the CLI routing through the Engine."""
import os
import tempfile
import threading
import types

import jax
import numpy as np
import pytest

from repro.api import (ASP, BSP, ClusterSpec, Engine, PartitionSpec, Plan,
                       RunSpec, TrainReport, UNBOUNDED_D, WSP, get_preset,
                       list_presets)
from repro.configs import ARCHS, reduced
from repro.core.param_server import ParameterServer
from repro.core.wave import build_local_wave_step
from repro.models import lm
from repro.optim import make_optimizer
from repro.runtime.checkpoint import latest_checkpoint, load_checkpoint

CFG = reduced(ARCHS["qwen3-0.6b"], num_layers=2, d_model=32, d_ff=64,
              vocab_size=256, num_heads=2, num_kv_heads=2, head_dim=16,
              num_microbatches=2)


def _setup(lr=0.3):
    params, _ = lm.init_params(CFG, jax.random.PRNGKey(0))
    opt = make_optimizer("sgd", lr)
    step = build_local_wave_step(CFG, CFG.num_microbatches, opt)
    return params, opt, step


# ---------------------------------------------------------------------------
# Plan validation (fail where the scenario is written)
# ---------------------------------------------------------------------------
def test_plan_validates_at_construction():
    with pytest.raises(ValueError, match="D must be"):
        Plan(sync=WSP(D=-1))
    with pytest.raises(ValueError, match="pull_every"):
        Plan(sync=WSP(pull_every=0))
    with pytest.raises(ValueError, match="num_vw"):
        Plan(cluster=ClusterSpec(num_vw=0))
    with pytest.raises(ValueError, match="speeds has"):
        Plan(cluster=ClusterSpec(num_vw=2, speeds=(0.1,)))
    with pytest.raises(ValueError, match="unknown backend"):
        Plan(run=RunSpec(backend="mpi"))
    with pytest.raises(ValueError, match="two spellings"):
        Plan(run=RunSpec(codec="int8", compression_ratio=0.5))
    with pytest.raises(ValueError, match="unknown codec"):
        Plan(run=RunSpec(codec="zstd"))
    with pytest.raises(ValueError, match="topology"):
        Plan(cluster=ClusterSpec(num_vw=2, topology="bogus-spec"))
    with pytest.raises(ValueError, match="not divisible"):
        Plan(arch=CFG, run=RunSpec(batch=5))
    with pytest.raises(ValueError, match="outside the fleet"):
        Plan(cluster=ClusterSpec(num_vw=2, fail_at={5: 3}))


def test_plan_validates_spmd_mesh():
    with pytest.raises(ValueError, match="arch is required|Plan.arch"):
        Plan(run=RunSpec(backend="spmd"))
    # stages*tp must divide the device count
    with pytest.raises(ValueError, match="does not divide"):
        Plan(arch=CFG, run=RunSpec(backend="spmd"),
             partition=PartitionSpec(stages=2, tp=1, data=1, devices=3))
    with pytest.raises(ValueError, match="data\\*stages\\*tp"):
        Plan(arch=CFG, run=RunSpec(backend="spmd"),
             partition=PartitionSpec(stages=2, tp=1, data=2, devices=8))
    # the jitted path is D=0 by construction
    with pytest.raises(ValueError, match="D = 0"):
        Plan(arch=CFG, sync=WSP(D=2), run=RunSpec(backend="spmd"),
             partition=PartitionSpec(stages=2, tp=1, data=1, devices=2))
    with pytest.raises(ValueError, match="async_push"):
        Plan(arch=CFG, sync=WSP(D=0, async_push=True),
             run=RunSpec(backend="spmd"),
             partition=PartitionSpec(stages=2, tp=1, data=1, devices=2))


def test_plan_rejects_knobs_the_backend_would_drop():
    # BSP all-reduces raw deltas: no codec, no per-worker failure injection
    with pytest.raises(ValueError, match="BSP loop all-reduces"):
        Plan(sync=BSP(), run=RunSpec(codec="topk:0.25"))
    with pytest.raises(ValueError, match="speeds only"):
        Plan(sync=BSP(), cluster=ClusterSpec(num_vw=2, fail_at={0: 1}))
    # the jitted spmd backend reduces in-graph: no PS-path modeling
    with pytest.raises(ValueError, match="reduces in-graph"):
        Plan(arch=CFG, run=RunSpec(backend="spmd", codec="int8"),
             partition=PartitionSpec(stages=2, tp=1, data=1, devices=2))
    with pytest.raises(ValueError, match="reduces in-graph"):
        Plan(arch=CFG, run=RunSpec(backend="spmd"),
             cluster=ClusterSpec(num_vw=1, topology="2node"),
             partition=PartitionSpec(stages=2, tp=1, data=1, devices=2))
    with pytest.raises(ValueError, match="threaded fleet"):
        Plan(arch=CFG, run=RunSpec(backend="spmd"),
             cluster=ClusterSpec(num_vw=2, speeds=(0.0, 0.5)),
             partition=PartitionSpec(stages=2, tp=1, data=1, devices=2))
    # the threads backend never factors a mesh
    with pytest.raises(ValueError, match="spmd mesh"):
        Plan(arch=CFG, partition=PartitionSpec(stages=4, data=2))
    # an explicit shape must agree with the run's loader shapes
    from repro.configs import ShapeConfig
    with pytest.raises(ValueError, match="disagrees"):
        Plan(arch=CFG, shape=ShapeConfig("x", 128, 8, "train"),
             run=RunSpec(backend="spmd", seq=64, batch=4),
             partition=PartitionSpec(stages=2, tp=1, data=1, devices=2))


def test_plan_replace_nested():
    plan = Plan(arch=CFG, sync=WSP(D=1))
    p2 = plan.replace(sync__D=3, run__max_waves=7,
                      cluster=ClusterSpec(num_vw=4))
    assert (p2.sync.D, p2.run.max_waves, p2.cluster.num_vw) == (3, 7, 4)
    assert plan.sync.D == 1                    # original untouched (frozen)


def test_asp_is_unbounded_wsp():
    assert isinstance(ASP(), WSP)
    assert ASP().D == UNBOUNDED_D
    assert "inf" in ASP().describe()


# ---------------------------------------------------------------------------
# TrainReport.loss_curve regression: sort by wall clock only
# ---------------------------------------------------------------------------
def test_loss_curve_sorts_by_time_only():
    """Tuple-sorting fell through to the worker id on wall-clock ties; with
    mixed-type ids that raised TypeError, and with string ids it reordered
    losses by name rather than time."""
    rep = TrainReport(losses=[(1.0, "vw9", 3.0), (1.0, 2, 4.0),
                              (0.5, "vw1", 5.0)])
    xs, ys = rep.loss_curve()                  # must not raise
    assert list(xs) == [0.5, 1.0, 1.0]
    assert ys[0] == 5.0
    # stable for ties: original append order preserved
    assert list(ys[1:]) == [3.0, 4.0]


# ---------------------------------------------------------------------------
# parity: the legacy constructors are shims over the same Engine
# ---------------------------------------------------------------------------
def test_engine_matches_legacy_wsp_trainer():
    """Engine.fit() with SyncPolicy=WSP(D) and the deprecated
    WSPTrainer.run() produce identical loss curves and final PS params on a
    seeded single-worker config (single worker => fully deterministic)."""
    from repro.runtime.trainer import WSPTrainer
    params, opt, step = _setup()
    plan = Plan(cluster=ClusterSpec(num_vw=1),
                sync=WSP(D=1, pull_every=2),
                run=RunSpec(max_waves=6, batch=8, seq=32,
                            vocab=CFG.vocab_size))
    eng = Engine(plan, params=params, wave_step=step, optimizer=opt)
    rep_new = eng.fit()
    with pytest.deprecated_call():
        tr = WSPTrainer(params, step, opt, num_vw=1, D=1, pull_every=2,
                        batch=8, seq=32, vocab=CFG.vocab_size, max_waves=6)
    rep_old = tr.run()
    np.testing.assert_array_equal(rep_new.loss_curve()[1],
                                  rep_old.loss_curve()[1])
    for a, b in zip(eng.ps.flat, tr.ps.flat):
        np.testing.assert_array_equal(a, b)


def test_engine_matches_legacy_bsp_baseline():
    from repro.runtime.trainer import bsp_allreduce_baseline
    params, opt, step = _setup()
    plan = Plan(cluster=ClusterSpec(num_vw=2), sync=BSP(),
                run=RunSpec(max_waves=5, batch=8, seq=32,
                            vocab=CFG.vocab_size))
    rep_new = Engine(plan, params=params, wave_step=step,
                     optimizer=opt).fit()
    with pytest.deprecated_call():
        rep_old = bsp_allreduce_baseline(params, step, opt, num_vw=2,
                                         batch=8, seq=32,
                                         vocab=CFG.vocab_size, max_waves=5)
    np.testing.assert_array_equal(rep_new.loss_curve()[1],
                                  rep_old.loss_curve()[1])


def test_threads_fit_is_single_shot():
    params, opt, step = _setup()
    plan = Plan(cluster=ClusterSpec(num_vw=1), sync=WSP(D=1),
                run=RunSpec(max_waves=2, batch=8, seq=32,
                            vocab=CFG.vocab_size))
    eng = Engine(plan, params=params, wave_step=step, optimizer=opt)
    eng.fit()
    with pytest.raises(RuntimeError, match="already ran"):
        eng.fit()                 # would return an empty report otherwise


@pytest.mark.parametrize("allow_degraded", [False, True])
def test_worker_error_fails_fit(allow_degraded):
    """A virtual worker that dies of an ordinary exception (a device out
    of memory, a compiler refusal) fails fit() even where degraded runs
    are allowed: no FaultPolicy recovers from it. Its peer is not left
    waiting at the staleness gate."""
    from repro.api import FaultPolicy
    calls = []

    def flaky_step(params, opt_state, x, y):
        calls.append(1)
        if len(calls) == 3:
            raise MemoryError("RESOURCE_EXHAUSTED: out of device memory")
        return {"w": np.ones(4, np.float32)}, opt_state, 1.0

    opt = types.SimpleNamespace(init=lambda p: None)
    plan = Plan(cluster=ClusterSpec(num_vw=2), sync=WSP(D=1),
                run=RunSpec(max_waves=6, batch=2, seq=8, vocab=16),
                fault_policy=FaultPolicy(allow_degraded=allow_degraded,
                                         gate_timeout_s=30.0))
    eng = Engine(plan, params={"w": np.zeros(4, np.float32)},
                 wave_step=flaky_step, optimizer=opt)
    with pytest.raises(RuntimeError, match="died.*RESOURCE_EXHAUSTED") as ei:
        eng.fit()
    assert isinstance(ei.value.__cause__, MemoryError)
    assert sum(w.exception is not None for w in eng.workers.values()) == 1


def test_bsp_checkpoints_and_resumes():
    """The BSP loop honors ckpt_dir/ckpt_every/resume like the other
    backends (checkpoint at the cadence AND at end of run, numbering
    continued across resume)."""
    def unit_step(params, opt_state, x, y):
        return {"w": np.ones(4, np.float32)}, opt_state, 1.0

    opt = types.SimpleNamespace(init=lambda p: None)
    with tempfile.TemporaryDirectory() as d:
        plan = Plan(cluster=ClusterSpec(num_vw=2), sync=BSP(),
                    run=RunSpec(max_waves=3, batch=2, seq=8, vocab=16,
                                ckpt_dir=d, ckpt_every=2))
        Engine(plan, params={"w": np.zeros(4, np.float32)},
               wave_step=unit_step, optimizer=opt).fit()
        # wave 2 (cadence) and wave 3 (end of run, off-cadence)
        assert sorted(os.listdir(d)) == ["step_00000002", "step_00000003"]
        Engine(plan.replace(run__resume=True, run__max_waves=2),
               params={"w": np.zeros(4, np.float32)},
               wave_step=unit_step, optimizer=opt).fit()
        out, meta = load_checkpoint(latest_checkpoint(d),
                                    {"params": {"w": np.zeros(4)}})
        assert meta["step"] == 5   # numbering continued: 3 restored + 2 new
        # averaged unit deltas: +1 per wave, so weights == total waves
        np.testing.assert_array_equal(out["params"]["w"], np.full(4, 5.0))
        # explicit save() also carries the continued numbering (not step 0)
        eng = Engine(plan.replace(run__resume=True, run__max_waves=1,
                                  run__ckpt_every=0),
                     params={"w": np.zeros(4, np.float32)},
                     wave_step=unit_step, optimizer=opt)
        eng.fit()
        assert eng.save().endswith("step_00000006")


def test_bsp_rejects_rejoin():
    params, opt, step = _setup()
    plan = Plan(cluster=ClusterSpec(num_vw=2), sync=BSP(),
                run=RunSpec(max_waves=2, batch=8, seq=32,
                            vocab=CFG.vocab_size))
    with pytest.raises(ValueError, match="no PS"):
        Engine(plan, params=params, wave_step=step,
               optimizer=opt).fit(rejoin_failed_after=0.1)
    # same contract on the spmd backend: unsupported, so loud
    spmd_plan = Plan(arch=CFG, sync=WSP(D=0),
                     partition=PartitionSpec(stages=2, tp=1, data=1,
                                             devices=2),
                     run=RunSpec(backend="spmd", max_waves=1))
    with pytest.raises(ValueError, match="no workers to rejoin"):
        Engine(spmd_plan).fit(rejoin_failed_after=0.1)


def test_asp_fast_worker_never_gated():
    params, opt, step = _setup()
    plan = Plan(cluster=ClusterSpec(num_vw=2, speeds=(0.0, 0.08)),
                sync=ASP(),
                run=RunSpec(max_waves=4, batch=4, seq=32,
                            vocab=CFG.vocab_size))
    rep = Engine(plan, params=params, wave_step=step, optimizer=opt).fit()
    assert rep.wait_seconds["vw0"] < 0.05      # gate disabled at D=inf


def test_engine_step_api():
    params, opt, step = _setup()
    plan = Plan(cluster=ClusterSpec(num_vw=1), sync=WSP(D=1),
                run=RunSpec(max_waves=3, batch=8, seq=32,
                            vocab=CFG.vocab_size))
    eng = Engine(plan, params=params, wave_step=step, optimizer=opt)
    losses = [eng.step() for _ in range(3)]
    assert all(isinstance(l, float) for l in losses)
    assert eng.ps.clock.state.clocks == {"vw0": 3}


def test_engine_requires_arch_or_injection():
    with pytest.raises(ValueError, match="inject"):
        Engine(Plan())


def test_step_matches_fit_including_pull_every():
    """Driving a Plan wave-by-wave through step() must reproduce fit()'s
    loss sequence and final PS params exactly, including the pull_every
    weight handling (single worker => fully deterministic)."""
    params, opt, step = _setup()
    plan = Plan(cluster=ClusterSpec(num_vw=1), sync=WSP(D=1, pull_every=2),
                run=RunSpec(max_waves=4, batch=8, seq=32,
                            vocab=CFG.vocab_size))
    eng_fit = Engine(plan, params=params, wave_step=step, optimizer=opt)
    rep = eng_fit.fit()
    eng_step = Engine(plan, params=params, wave_step=step, optimizer=opt)
    losses = [eng_step.step() for _ in range(4)]
    np.testing.assert_array_equal(np.asarray(losses), rep.loss_curve()[1])
    for a, b in zip(eng_step.ps.flat, eng_fit.ps.flat):
        np.testing.assert_array_equal(a, b)


def test_engine_step_rejects_bsp():
    """step() must not silently substitute a WSP policy for a BSP plan
    (fit() and step() on one Plan must agree on the synchronization
    model)."""
    params, opt, step = _setup()
    plan = Plan(cluster=ClusterSpec(num_vw=2), sync=BSP(),
                run=RunSpec(max_waves=2, batch=8, seq=32,
                            vocab=CFG.vocab_size))
    eng = Engine(plan, params=params, wave_step=step, optimizer=opt)
    with pytest.raises(ValueError, match="fit"):
        eng.step()


# ---------------------------------------------------------------------------
# presets
# ---------------------------------------------------------------------------
def test_presets_all_build_valid_plans():
    names = set(list_presets())
    assert {"single_node", "paper_hetero", "whimpy_1gbe",
            "bsp_baseline", "spmd_tiny"} <= names
    for name in names:
        plan = get_preset(name)
        assert isinstance(plan, Plan)          # validated at construction
    with pytest.raises(KeyError, match="unknown preset"):
        get_preset("nope")


def test_preset_override_and_run():
    plan = get_preset("single_node", run__max_waves=4, sync__D=0)
    assert plan.run.max_waves == 4 and plan.sync.D == 0
    rep = Engine(plan).fit()
    assert rep.waves == 8                      # 2 workers x 4 waves


# ---------------------------------------------------------------------------
# checkpointing under async push (satellite: in-flight pushes must be
# atomic with respect to snapshots)
# ---------------------------------------------------------------------------
def test_ps_snapshot_atomic_with_concurrent_pushes():
    """checkpoint_state() must capture weights containing exactly the waves
    the clocks count: with unit deltas, snapshot weights == sum of clocks,
    always. Without the PS snapshot lock a push could land between the
    weight copy and the clock copy (push lost on resume) or vice versa
    (double-applied)."""
    ps = ParameterServer({"w": np.zeros(64, np.float32)}, D=UNBOUNDED_D)
    delta = {"w": np.ones(64, np.float32)}
    for wid in ("vw0", "vw1"):
        ps.register(wid)

    def pusher(wid):
        for _ in range(40):
            ps.push_wave(wid, delta)

    threads = [threading.Thread(target=pusher, args=(w,))
               for w in ("vw0", "vw1")]
    for t in threads:
        t.start()
    violations = []
    while any(t.is_alive() for t in threads):
        snap, meta = ps.checkpoint_state()
        want = float(meta["push_count"])
        got = np.asarray(snap["w"])
        if not np.all(got == want):
            violations.append((want, float(got[0])))
    for t in threads:
        t.join()
    assert not violations, violations[:5]
    assert ps.clock.state.clocks == {"vw0": 40, "vw1": 40}
    assert ps.push_count == 80


def test_checkpoint_not_lost_or_doubled_under_async_push(tmp_path=None):
    """End-to-end: periodic checkpoints taken while async outbox pushes are
    in flight (slow simulated link). Every checkpoint written must satisfy
    weights == sum(clock) * unit-delta, and resuming from the latest one
    continues exactly."""
    from repro.dist.topology import ClusterTopology, LinkSpec, NVLINK, Pod
    slow = LinkSpec("slow", 1e6, 0.02)         # ~20ms per push in flight
    topo = ClusterTopology([Pod("node0", ("vw0",), NVLINK),
                            Pod("node1", ("vw1",), NVLINK)], inter=slow)

    def unit_step(params, opt_state, x, y):
        return {"w": np.ones(8, np.float32)}, opt_state, 1.0

    opt = types.SimpleNamespace(init=lambda p: None)
    with tempfile.TemporaryDirectory() as d:
        plan = Plan(cluster=ClusterSpec(num_vw=2, topology=topo,
                                        time_scale=1.0),
                    sync=WSP(D=4, pull_every=2, async_push=True),
                    run=RunSpec(max_waves=6, batch=2, seq=8, vocab=16,
                                ckpt_dir=d, ckpt_every=1))
        eng = Engine(plan, params={"w": np.zeros(8, np.float32)},
                     wave_step=unit_step, optimizer=opt)
        eng.fit()
        steps = sorted(p for p in os.listdir(d) if p.startswith("step_"))
        assert steps, "no periodic checkpoints written"
        for s in steps:
            out, meta = load_checkpoint(
                os.path.join(d, s), {"params": {"w": np.zeros(8)}})
            # weights contain exactly the pushes the meta counts — an
            # in-flight push is either fully in (weights AND count) or
            # fully out, never half
            want = float(meta["push_count"])
            np.testing.assert_array_equal(out["params"]["w"],
                                          np.full(8, want))
        # resume from the latest checkpoint and push two more waves each
        plan2 = plan.replace(run__max_waves=2, run__resume=True,
                             run__ckpt_every=0)
        eng2 = Engine(plan2, params={"w": np.zeros(8, np.float32)},
                      wave_step=unit_step, optimizer=opt)
        eng2.fit()
        _, meta = load_checkpoint(latest_checkpoint(d),
                                  {"params": {"w": np.zeros(8)}})
        restored = float(meta["push_count"])
        np.testing.assert_array_equal(
            eng2.ps.flat[0], np.full(8, restored + 4.0, np.float32))


def test_resume_checkpoint_numbering_monotone():
    """Post-resume checkpoints must continue the restored step numbering:
    if they restarted at zero, latest_checkpoint() would resolve to the
    stale pre-resume checkpoint and discard all post-resume progress.
    With unit deltas, every checkpoint's weights == its step number."""
    def unit_step(params, opt_state, x, y):
        return {"w": np.ones(4, np.float32)}, opt_state, 1.0

    opt = types.SimpleNamespace(init=lambda p: None)
    with tempfile.TemporaryDirectory() as d:
        plan = Plan(cluster=ClusterSpec(num_vw=1), sync=WSP(D=1),
                    run=RunSpec(max_waves=3, batch=2, seq=8, vocab=16,
                                ckpt_dir=d, ckpt_every=1))
        Engine(plan, params={"w": np.zeros(4, np.float32)},
               wave_step=unit_step, optimizer=opt).fit()
        first = latest_checkpoint(d)
        Engine(plan.replace(run__resume=True, run__max_waves=2),
               params={"w": np.zeros(4, np.float32)},
               wave_step=unit_step, optimizer=opt).fit()
        assert latest_checkpoint(d) > first        # numbering continued
        for s in sorted(os.listdir(d)):
            step = int(s.removeprefix("step_"))
            out, _ = load_checkpoint(os.path.join(d, s),
                                     {"params": {"w": np.zeros(4)}})
            np.testing.assert_array_equal(out["params"]["w"],
                                          np.full(4, float(step)))


def test_spmd_resume_with_repartitioned_stages():
    """The spmd backend re-factors stages from the PartitionSpec; the
    resume path must build its checkpoint template from that same arch
    (padded layer counts differ when stages does not divide num_layers)."""
    cfg3 = reduced(ARCHS["qwen3-0.6b"], num_layers=3, d_model=32, d_ff=64,
                   vocab_size=256, num_heads=2, num_kv_heads=2, head_dim=16,
                   num_microbatches=2, stages=2)
    assert cfg3.padded_layers == 4                 # 2 stages pad 3 -> 4
    with tempfile.TemporaryDirectory() as d:
        plan = Plan(arch=cfg3,
                    partition=PartitionSpec(stages=1, tp=1, data=1),
                    sync=WSP(D=0),
                    run=RunSpec(backend="spmd", max_waves=1, batch=4,
                                seq=16, ckpt_dir=d, ckpt_every=1))
        Engine(plan).fit()                         # 1-stage arch: 3 layers
        eng2 = Engine(plan.replace(run__resume=True))
        eng2.fit()                                 # restore must not reshape
        assert eng2._step_offset == 1
        assert latest_checkpoint(d).endswith("step_00000002")


def test_engine_save_restore_roundtrip():
    params, opt, step = _setup()
    with tempfile.TemporaryDirectory() as d:
        plan = Plan(cluster=ClusterSpec(num_vw=1), sync=WSP(D=1),
                    run=RunSpec(max_waves=3, batch=8, seq=32,
                                vocab=CFG.vocab_size, ckpt_dir=d))
        eng = Engine(plan, params=params, wave_step=step, optimizer=opt)
        eng.fit()
        path = eng.save()
        trained = [f.copy() for f in eng.ps.flat]
        eng2 = Engine(plan, params=params, wave_step=step, optimizer=opt)
        meta = eng2.restore(path)
        assert meta["clocks"] == {"vw0": 3}
        eng2._ensure_ps(plan.sync)
        for a, b in zip(eng2.ps.flat, trained):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# CLI: both launch modes route through the same Engine
# ---------------------------------------------------------------------------
def test_launch_train_wsp_routes_through_engine(capsys):
    from repro.launch import train
    train.main(["--mode", "wsp", "--reduced", "--layers", "2",
                "--d-model", "32", "--waves", "2", "--num-vw", "1",
                "--D", "0", "--batch", "4", "--seq", "32"])
    out = capsys.readouterr().out
    assert "waves=2" in out and "last_loss=" in out


def test_launch_train_spmd_routes_through_engine(capsys):
    # mesh 1,1,1 fits the single CPU device of the pytest process
    from repro.launch import train
    train.main(["--mode", "spmd", "--reduced", "--layers", "2",
                "--d-model", "32", "--waves", "2", "--mesh", "1,1,1",
                "--batch", "4", "--seq", "32"])
    out = capsys.readouterr().out
    assert "mesh=(1,1,1)" in out and "wave " in out


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """The entry points' persistent compile cache follows
    JAX_COMPILATION_CACHE_DIR when it is set (setting nothing itself), and
    a fixed <checkout>/.jax_cache otherwise."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    calls = []
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert calls == []
    assert jax.config.jax_compilation_cache_dir == before


def test_launch_topology_list(capsys):
    from repro.launch import train
    train.main(["--topology", "list"])
    out = capsys.readouterr().out
    assert "<k>node[:LINK]" in out and "paper" in out


# ---------------------------------------------------------------------------
# make_topology validation (satellite)
# ---------------------------------------------------------------------------
def test_make_topology_helpful_errors():
    from repro.dist.topology import ETH_1G, IB_100G, make_topology
    with pytest.raises(ValueError, match="Known specs"):
        make_topology("bogus", 2)
    with pytest.raises(ValueError, match="integer k"):
        make_topology("xnode", 2)
    with pytest.raises(ValueError, match="unknown inter-node link"):
        make_topology("2node:foo", 2)
    with pytest.raises(ValueError, match="at least one node"):
        make_topology("0node", 2)
    assert make_topology("2node:eth1", 4).inter is ETH_1G
    assert make_topology("2node:ib", 4).inter is IB_100G
    assert make_topology("none", 4) is None
