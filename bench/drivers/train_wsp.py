"""Driver `train_wsp`: HetPipe's WSP training through the program's
Engine.fit() on the threaded fleet (virtual workers as threads, a host
parameter server, the jitted local wave step).

One Engine runs for the whole process. Set-up: its fit() starts on a
background thread and runs until `warm_pushes` waves have landed on the
parameter server (the wave step compiles or loads from the compile cache in
the first). The window opens at that push and closes at the first push
`seconds` later, so it starts and ends at wave boundaries; then the
driver sets the Engine's stop_event and deregisters the workers, which
releases any worker waiting at the staleness gate, and fit() returns.

Hooks on the parameter server record, for the check: the order in which
pushes were applied, how many of them each leaf of each pull held, the
rows and loss of each wave, the first wave's delta (on the host), and the
server's weights once `check_steps` pushes have landed. None of them takes
a lock the program does not: a pull is consistent per leaf only, so which
pushes it held is read leaf by leaf from the version the server stamps on
each cached leaf, and the weights are copied inside the apply of the
`check_steps`-th push, under the lock the server already holds there.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from bench import common


class VersionLog(list):
    """The parameter server's per-leaf pull cache, noting the version of
    every leaf snapshot put into it: the pushes applied to the leaf's
    shard, which (pushes are applied one at a time, in order) are the first
    that many pushes. Keyed by the snapshot's id; a pulled leaf is alive
    from its caching to its lookup, so the id cannot be reused between."""

    def __init__(self, items, hooks):
        super().__init__(items)
        self.hooks = hooks

    def __setitem__(self, i, entry):
        seen = self.hooks.versions
        if seen is not None and entry is not None:
            seen[id(entry[1])] = entry[0]
        super().__setitem__(i, entry)


class Hooks:
    def __init__(self, check_steps: int):
        self.check_steps = check_steps
        self.cv = threading.Condition()
        self.pushes = []            # (time applied, wid), in apply order
        self.versions = {}          # id(leaf snapshot) -> pushes it holds
        self.pull_base = {}         # wid -> per leaf, pushes its pull held
        self.calls = {}             # wid -> [wave record]
        self.snapshot = None        # server weights after check_steps
        self.error = None

    def attach(self, ps):
        import jax
        ann = jax.profiler.TraceAnnotation
        clock = ps.clock
        complete, pull, push = (clock.complete_wave_if_registered, ps.pull,
                                ps.push_wave)

        def on_complete(wid):
            out = complete(wid)      # under the server's snapshot lock
            if ps.push_count == self.check_steps:
                self.snapshot = [f.copy() for f in ps.flat]
                self.versions = None     # every checked wave has pulled
            with self.cv:
                self.pushes.append((time.monotonic(), wid))
                self.cv.notify_all()
            return out

        def on_pull(wid=None):
            with ann("ps.pull"):
                out = pull(wid)
            seen = self.versions
            if wid is not None and seen is not None:
                self.pull_base[wid] = [seen[id(l)]
                                       for l in jax.tree.leaves(out)]
            return out

        def on_push(wid, deltas):
            with ann("ps.push_wave"):
                return push(wid, deltas)

        clock.complete_wave_if_registered = on_complete
        ps.pull, ps.push_wave = on_pull, on_push
        ps._leaf_cache = VersionLog(ps._leaf_cache, self)

    def wrap_step(self, inner):
        import jax

        def step(params, opt_state, x, y):
            wid = threading.current_thread().name
            with jax.profiler.TraceAnnotation("wave_step"):
                deltas, opt_state, loss = inner(params, opt_state, x, y)
                loss = float(loss)
            calls = self.calls.setdefault(wid, [])
            rec = {"base": self.pull_base.get(wid, 0), "loss": loss}
            if len(self.pushes) < self.check_steps:
                rec.update(x=np.array(x), y=np.array(y))
            if not self.pushes:         # the first push is one of these
                rec["deltas"] = [np.array(d) for d in jax.tree.leaves(deltas)]
            calls.append(rec)
            return deltas, opt_state, loss

        return step

    def steps(self):
        """Wave records of the first check_steps pushes, in apply order."""
        seen, out = {}, []
        for _, wid in self.pushes[:self.check_steps]:
            k = seen.get(wid, 0)
            seen[wid] = k + 1
            out.append(self.calls[wid][k])
        return out

    def wait(self, cond, timeout: float, fit_thread):
        with self.cv:
            while not cond():
                if self.error is not None or not fit_thread.is_alive():
                    raise RuntimeError(f"fit() ended before the window "
                                       f"closed: {self.error!r}")
                if not self.cv.wait(timeout=min(timeout, 1.0)):
                    timeout -= 1.0
                    if timeout <= 0:
                        raise TimeoutError("no wave landed in time")


def bench_engine(Engine, hooks: Hooks):
    import jax

    class BenchEngine(Engine):
        def _ensure_ps(self, policy):
            super()._ensure_ps(policy)
            if not getattr(self.ps, "_bench_hooked", False):
                hooks.attach(self.ps)
                self.ps._bench_hooked = True

        def _loader(self, i, num_vw):
            loader = super()._loader(i, num_vw)
            nxt = loader.next

            def annotated():
                with jax.profiler.TraceAnnotation("loader.next"):
                    return nxt()

            loader.next = annotated
            return loader

    return BenchEngine


def plan_for(arch, traffic: dict, seed: int):
    from repro.api import ClusterSpec, Plan, RunSpec, WSP
    plan = Plan(arch=arch, cluster=ClusterSpec(num_vw=traffic["num_vw"]),
                sync=WSP(D=traffic["D"]),
                run=RunSpec(max_waves=1 << 30, batch=traffic["batch"],
                            seq=traffic["seq"], optimizer=traffic["optimizer"],
                            lr=traffic["lr"], data_seed=seed))
    if plan.num_microbatches != traffic["microbatches"]:
        raise ValueError(f"{arch.name} packs {plan.num_microbatches} "
                         f"microbatches, the traffic asks for "
                         f"{traffic['microbatches']}")
    return plan


def run(cfg: dict, traffic: dict, cell: dict, *, seed: int, seconds: float,
        trace: bool, t_start: float, counter, tracer_dir=None,
        readings=("program",), wave_step=None) -> dict:
    """One run of a training cell. Returns the record run.py reduces.
    `readings` adds the control ("control") and the half-batch fault
    ("half_batch") to the program's numbers (calibration only);
    `wave_step` replaces the program's own (calibration reuses one compiled
    step over seeds; tests plant faults in it)."""
    import jax

    from repro.api import Engine
    from repro.core import wave
    from repro.models import lm
    from repro.obs import Tracer
    from repro.optim import make_optimizer

    t_driver = time.monotonic()
    arch = common.arch_for(cfg)
    plan = plan_for(arch, traffic, seed)
    params = common.make_params(lm.param_shapes(arch), arch.num_layers, seed)
    jax.block_until_ready(params)
    t_weights = time.monotonic()
    hooks = Hooks(traffic["check_steps"])
    if wave_step is None:
        wave_step = wave.build_local_wave_step(
            arch, plan.num_microbatches,
            make_optimizer(traffic["optimizer"], traffic["lr"]))
    tracer = Tracer() if trace else None
    eng = bench_engine(Engine, hooks)(plan, params=params,
                                      wave_step=hooks.wrap_step(wave_step),
                                      tracer=tracer)
    result = {}

    def fit():
        try:
            result["report"] = eng.fit()
        except BaseException as e:      # re-raised on the main thread
            hooks.error = e
            with hooks.cv:
                hooks.cv.notify_all()

    th = threading.Thread(target=fit, name="fit", daemon=True)
    th.start()
    warm = max(traffic["warm_pushes"], traffic["check_steps"])
    hooks.wait(lambda: len(hooks.pushes) >= warm, 1200, th)
    t0 = hooks.pushes[warm - 1][0]
    length = min(seconds, traffic["trace_seconds"]) if trace else seconds
    if trace:
        common.start_trace(tracer_dir)
        t0 = time.monotonic()
    c0 = counter.compiles
    if length > 0:
        hooks.wait(lambda: hooks.pushes[-1][0] >= t0 + length, 1200, th)
    t1 = hooks.pushes[-1][0] if length > 0 else t0
    if trace:
        jax.profiler.stop_trace()
    compiles = counter.compiles - c0
    eng.stop_event.set()
    for wid in list(eng.workers):
        eng.ps.deregister(wid)
    th.join(timeout=600)
    if th.is_alive():
        raise RuntimeError("fit() did not return after the stop")
    if hooks.error is not None:
        raise hooks.error
    dev = common.device_record(jax.devices())
    landed = [p for p in hooks.pushes if t0 < p[0] <= t1]
    tokens = len(landed) * traffic["batch"] * traffic["seq"]
    events = [e for e in (tracer.events() if tracer else [])
              if t0 <= e[3] <= t1]
    steps = hooks.steps()
    lr = traffic["lr"]
    from bench.reference import model, train_check
    prog = ([s["loss"] for s in steps],
            train_check.norms(-d / lr for d in steps[0]["deltas"]),
            train_check.norms(s - np.asarray(w).ravel() for s, w in
                              zip(hooks.snapshot, jax.tree.leaves(params))))
    for calls in hooks.calls.values():
        for c in calls:
            c.pop("deltas", None)
    hooks.snapshot = None
    del eng, result
    gc.collect()

    t_check = time.monotonic()
    ref = train_check.replay(cfg, params, steps, lr)
    check = train_check.compare(ref, prog)
    extra = {}
    if "control" in readings:
        extra["control"] = train_check.compare(ref, train_check.replay(
            cfg, params, steps, lr, model.CONTROL))
    if "half_batch" in readings:
        extra["half_batch"] = train_check.compare(ref, train_check.replay(
            cfg, params, steps, lr, rows=slice(0, traffic["batch"] // 2)))
    check_s = time.monotonic() - t_check
    waves = len(landed)
    window_s = t1 - t0
    return {
        "kind": "train", "t0": t0, "t1": t1, "setup_s": t0 - t_start,
        "compiles_in_window": compiles, "device": dev, "events": events,
        "attempted": len(hooks.pushes), "failed": 0,
        "waves_in_window": waves, "window_s": window_s,
        "tokens_per_wave": traffic["batch"] * traffic["seq"],
        "cfg": cfg, "arch": arch, "traffic": traffic,
        "check": check, "readings": extra,
        "extra_lines": {"setup_split_s": {
                            "imports": t_driver - t_start,
                            "weights": t_weights - t_driver,
                            "first_push": hooks.pushes[0][0] - t_weights,
                            "warm_pushes": t0 - hooks.pushes[0][0]},
                        "pushes_in_window": waves, "check_s": check_s,
                        "landing_gaps_s": [b[0] - a[0] for a, b in zip(
                            hooks.pushes, hooks.pushes[1:])
                            if t0 <= a[0] and b[0] <= t1],
                        "losses_program": prog[0], "losses_reference": ref[0]},
        "check_losses": {"program": prog[0], "reference": ref[0]},
        "metrics": {
            "train_tokens_per_s": tokens / window_s if window_s > 0 else 0.0,
            "setup_s": t0 - t_start,
        },
    }
