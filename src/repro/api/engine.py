"""The Engine: one facade executing any Plan.

fit() dispatches on the Plan alone:

  backend='threads' + WSP/ASP   threaded virtual-worker fleet against the
                                sharded parameter server (true async, D >= 0,
                                stragglers, periodic checkpoint, elastic
                                fail/rejoin)
  backend='threads' + BSP       the synchronous AllReduce loop (ring
                                all-reduce of every wave's deltas, simulated
                                straggler-gated clock)
  backend='spmd'                the jitted pipelined wave step over a
                                (data, stage, tp) mesh (D = 0)

Serve-mode Plans (Plan.serve = ServeSpec) run through
prefill()/decode()/generate() with the same dispatch rule:

  backend='spmd'                the pipelined serve steps
                                (core.wave.build_prefill_step /
                                build_decode_step) on a (1, stage, tp) mesh
  backend='threads'             the non-pipelined lm.forward_ref cache path
                                (the CPU correctness oracle)

All backends share model materialization, data loaders and report assembly
(TrainReport / ServeReport), and step()/save()/restore() complete the
surface: single-wave stepping for interactive use, atomic checkpointing,
exact resume.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.api.plan import Plan
from repro.api.report import ServeReport, Telemetry, TrainReport
from repro.api.sync import BSP, WSP
from repro.core.param_server import ParameterServer
from repro.obs import NULL_TRACER, emit_pipeline_ticks
from repro.obs.metrics import SECONDS_BOUNDS
from repro.data.pipeline import MarkovLM, ShardedLoader
from repro.dist import collectives
from repro.dist.topology import make_topology
from repro.dist.transport import SimulatedTransport
from repro.runtime.checkpoint import (latest_checkpoint, load_checkpoint,
                                      save_checkpoint)
from repro.runtime.virtual_worker import VirtualWorker


class Engine:
    """Executes a Plan. Model artifacts (params / wave step / optimizer) are
    built from the Plan's ArchConfig by default; tests and the legacy shims
    may inject prebuilt ones instead."""

    def __init__(self, plan: Plan, *, params=None, wave_step=None,
                 optimizer=None, tracer=None):
        if not isinstance(plan, Plan):
            raise TypeError(f"Engine wants a Plan, got {type(plan).__name__}")
        if plan.arch is None and (params is None or wave_step is None
                                  or optimizer is None):
            raise ValueError("Plan.arch is unset: inject params, wave_step "
                             "and optimizer, or give the Plan an ArchConfig")
        self.plan = plan
        # the tracer is runtime state, not Plan state: the same frozen Plan
        # runs traced or untraced. It cascades into the PS, transport,
        # workers and scheduler this engine builds.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._params = params
        self._wave_step = wave_step
        self._optimizer = optimizer
        self.ps: Optional[ParameterServer] = None
        self.topology = None
        self.workers: dict[str, VirtualWorker] = {}
        self.stop_event = threading.Event()
        self.supervisor = None     # FleetSupervisor when fault-supervised
        self._injector = None      # lazy FaultInjector from plan.faults
        self.report: Optional[TrainReport] = None
        self._source = None
        self._step_ctx = None      # lazy state for step()
        self._spmd = None          # lazy state for the spmd backend
        self._serve = None         # lazy state for the serve surface
        self._serve_paged = None   # lazy paged (CacheStore) serve executors
        self._step_offset = 0      # waves already in a restored checkpoint
        self._fleet_ran = False    # the threaded fleet is single-shot
        self._bsp_wave = 0         # waves the BSP loop has run (this engine)

    # ------------------------------------------------------------------
    # materialization
    # ------------------------------------------------------------------
    def _model_arch(self):
        """The arch whose parameter shapes this engine trains: the spmd
        backend re-factors stages/tp from the PartitionSpec (padded layer
        count can change), the threads backend uses the arch as declared."""
        if self.plan.run.backend != "spmd":
            return self.plan.arch
        import dataclasses as dc
        plan = self.plan
        arch = dc.replace(plan.arch, stages=plan.stages, tp=plan.tp)
        if plan.partition.num_microbatches:
            arch = dc.replace(
                arch, num_microbatches=plan.partition.num_microbatches)
        return arch

    def _ensure_model(self):
        from repro.core import wave
        from repro.models import lm
        from repro.optim import make_optimizer
        plan, run = self.plan, self.plan.run
        if self._optimizer is None:
            self._optimizer = make_optimizer(run.optimizer, run.lr,
                                             run.weight_decay)
        if self._params is None:
            self._params, _ = lm.init_params(self._model_arch(),
                                             jax.random.PRNGKey(run.seed))
        if plan.serve is not None:
            return                 # no wave step / loader on the serve path
        if self._wave_step is None and run.backend != "spmd":
            self._wave_step = wave.build_local_wave_step(
                plan.arch, plan.num_microbatches, self._optimizer)
        if self._source is None:
            self._source = MarkovLM(plan.vocab, seed=run.data_seed)

    def fault_injector(self):
        """The run's FaultInjector, built once from Plan.faults (None when
        the Plan carries no fault scenario). Shared by every seam — the
        transport, the PS and the Scheduler consult the same per-path /
        per-push / per-step counters."""
        if self._injector is None and self.plan.faults is not None:
            from repro.faults import FaultInjector
            self._injector = FaultInjector(
                self.plan.faults, time_scale=self.plan.cluster.time_scale)
        return self._injector

    def _ensure_ps(self, policy: WSP):
        if self.ps is not None:
            return
        plan = self.plan
        topo = plan.cluster.topology
        if isinstance(topo, str):
            topo = make_topology(topo, plan.cluster.num_vw)
        self.topology = topo
        injector = self.fault_injector()
        fpol = plan.fault_policy
        transport = (SimulatedTransport(topo,
                                        time_scale=plan.cluster.time_scale,
                                        tracer=self.tracer,
                                        injector=injector, policy=fpol)
                     if topo is not None else None)
        if transport is None and injector is not None:
            from repro.dist.transport import NullTransport
            transport = NullTransport(injector=injector, policy=fpol)
        self.ps = ParameterServer(
            self._params, D=policy.D,
            compression_ratio=plan.run.compression_ratio,
            codec=plan.run.codec, transport=transport,
            tracer=self.tracer, injector=injector)

    def _loader(self, i: int, num_vw: int) -> ShardedLoader:
        run = self.plan.run
        return ShardedLoader(self._source, run.batch, run.seq, i, num_vw,
                             seed=17)

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------
    def _tick_plan(self):
        """(schedule, ticks) of the Plan's modeled intra-VW pipeline, or
        None when the Plan carries no arch (injected wave steps have no
        declared stage structure to render)."""
        if not self.tracer.enabled or self.plan.arch is None:
            return None
        from repro.core import wave
        arch = self._model_arch()
        return wave.tick_schedule(arch.stages, self.plan.num_microbatches,
                                  overlap=self.plan.run.overlap)

    def attach_telemetry(self, report):
        """Record end-of-run gauges (staleness bound, per-link traffic) and
        attach the metrics snapshot to `report` — no-op untraced."""
        if not self.tracer.enabled:
            return report
        m = self.tracer.metrics
        policy = self.plan.sync
        if isinstance(policy, WSP):
            m.gauge_set("wsp/D", policy.D)
        if self.ps is not None:
            stats = self.ps.transport.stats()
            for name, b in stats["bytes_by_link"].items():
                m.gauge_set(f"link/{name}/bytes", b)
            for name, s in stats["seconds_by_link"].items():
                m.gauge_set(f"link/{name}/modeled_s", s)
        report.telemetry = Telemetry.from_metrics(m)
        return report

    # ------------------------------------------------------------------
    # public surface
    # ------------------------------------------------------------------
    def fit(self, *, rejoin_failed_after: Optional[float] = None,
            callback: Optional[Callable] = None) -> TrainReport:
        """Run the Plan to completion and return its TrainReport.
        `callback(wave, loss, seconds)` is invoked per wave on backends with
        a central loop (bsp, spmd); the threaded fleet reports at the end."""
        plan = self.plan
        if plan.serve is not None:
            raise ValueError("this Plan describes serving (Plan.serve is "
                             "set); run it through Engine.generate() — "
                             "fit() trains")
        if plan.run.resume and plan.run.ckpt_dir:
            self.restore()
        with self.tracer.span("engine", "fit", backend=plan.run.backend,
                              sync=plan.sync.describe()):
            if plan.run.backend == "spmd":
                if rejoin_failed_after is not None:
                    raise ValueError("elastic rejoin is a feature of the "
                                     "threaded parameter-server fleet; the "
                                     "jitted spmd backend has no workers to "
                                     "rejoin")
                self.report = self._fit_spmd(callback=callback)
            else:
                self.report = plan.sync.execute(
                    self, rejoin_failed_after=rejoin_failed_after,
                    callback=callback)
        return self.attach_telemetry(self.report)

    def step(self):
        """One synchronous wave (single-worker semantics on the threads
        backend, one jitted step on spmd). Returns the wave's loss."""
        if self.plan.serve is not None:
            raise ValueError("step() drives a training wave; this Plan "
                             "serves — use prefill()/decode()/generate()")
        if self.plan.run.backend == "spmd":
            self._ensure_spmd()
            with self.tracer.span("engine", "step",
                                  wave=self._spmd["wave"]):
                return self._spmd_step()
        policy = self.plan.sync
        if not isinstance(policy, WSP):
            raise ValueError(
                f"step() drives the parameter-server runtime and supports "
                f"WSP/ASP policies (or the spmd backend); this Plan's "
                f"{policy.describe()} runs only through fit()")
        self._ensure_model()
        self._ensure_ps(policy)
        if self._step_ctx is None:
            wid = "vw0"
            self.ps.register(wid)
            self._step_ctx = {
                "wid": wid,
                "loader": self._loader(0, 1),
                "opt_state": self._optimizer.init(self.ps.pull()),
                "params": self.ps.pull(wid),
            }
        ctx = self._step_ctx
        wid = ctx["wid"]
        # raises the typed GateTimeout if the gate never opens
        self.ps.gate(wid, timeout=self.plan.fault_policy.gate_timeout_s)
        with self.tracer.span("engine", "step"):
            x, y = ctx["loader"].next()
            deltas, ctx["opt_state"], loss = self._wave_step(
                ctx["params"], ctx["opt_state"], x, y)
            wave = self.ps.push_wave(wid, deltas)
        # mirror VirtualWorker's weight handling so fit() and step() agree:
        # local weights see their own wave immediately, w_global is pulled
        # every pull_every waves
        if policy.pull_every != 1:
            ctx["params"] = jax.tree.map(
                np.add, ctx["params"], jax.tree.map(np.asarray, deltas))
        if policy.pull_every and wave % policy.pull_every == 0:
            ctx["params"] = self.ps.pull(wid)
        return float(loss)

    def save(self, ckpt_dir: Optional[str] = None) -> str:
        """Checkpoint the full training state atomically (PS weights + WSP
        clocks are snapshotted under the push lock, so an in-flight async
        push is either entirely in the checkpoint or entirely out)."""
        ckpt_dir = ckpt_dir or self.plan.run.ckpt_dir
        if not ckpt_dir:
            raise ValueError("no checkpoint directory: set run.ckpt_dir or "
                             "pass one to save()")
        if self.ps is not None:
            params, meta = self.ps.checkpoint_state()
            step = min(meta["clocks"].values()) if meta["clocks"] else \
                meta["push_count"]
            return save_checkpoint(ckpt_dir, self._step_offset + step,
                                   {"params": params}, meta)
        if self._spmd is not None:
            step = self._step_offset + self._spmd["wave"]
            params = jax.tree.map(np.asarray, self._spmd["params"])
            return save_checkpoint(ckpt_dir, step, {"params": params},
                                   {"wave": step})
        self._ensure_model()
        step = self._step_offset + self._bsp_wave
        return save_checkpoint(ckpt_dir, step, {"params": self._params},
                               {"wave": step})

    def restore(self, path: Optional[str] = None) -> Optional[dict]:
        """Load the latest (or given) checkpoint's weights into the engine;
        returns the checkpoint meta, or None if there is nothing to restore.
        Worker clocks restart at zero (max_waves counts waves of this run),
        but new checkpoints continue the restored step numbering so a later
        latest_checkpoint() never resolves to pre-resume state."""
        path = path or (latest_checkpoint(self.plan.run.ckpt_dir)
                        if self.plan.run.ckpt_dir else None)
        if path is None:
            return None
        self._ensure_model()
        out, meta = load_checkpoint(path, {"params": self._params})
        self._step_offset = int(meta.get("step", 0))
        self._params = out["params"]
        if self.ps is not None:
            leaves = [np.asarray(l).astype(np.float32).ravel()
                      for l in jax.tree.leaves(self._params)]
            self.ps.load_state_dict({"flat": leaves,
                                     "clocks": dict(self.ps.clock.state.clocks),
                                     "push_count": self.ps.push_count})
        if self._spmd is not None:
            # re-place with the mesh sharding (a bare device_put would
            # commit the whole tree to one device) and drop optimizer
            # moments computed for the pre-restore weights
            st = self._spmd
            st["params"] = self._shard_params(st["mesh"], st["pspecs"],
                                              self._params)
            with jax.set_mesh(st["mesh"]):
                st["opt_state"] = self._optimizer.init(st["params"])
        if self._serve is not None:
            st = self._serve
            st["params"] = (self._shard_params(st["mesh"], st["pspecs"],
                                               self._params)
                            if st["mode"] == "spmd" else self._params)
        return meta

    # ------------------------------------------------------------------
    # serve surface: prefill / decode / generate (Plan.serve = ServeSpec)
    # ------------------------------------------------------------------
    def _require_serve(self, what: str):
        if self.plan.serve is None:
            raise ValueError(f"{what}() serves requests; Plan.serve is "
                             f"unset — give the Plan a ServeSpec (train "
                             f"Plans run through fit())")

    def _serve_dtypes(self):
        from repro.models import lm
        run, sv = self.plan.run, self.plan.serve
        return lm.serve_dtypes(run.compute_dtype, sv.cache_dtype)

    def _ensure_serve(self):
        """Build the serve executors the Plan names: the pipelined mesh
        steps (backend='spmd') or the forward_ref cache path (threads)."""
        if self._serve is not None:
            return
        from repro.models import lm
        plan, run, sv = self.plan, self.plan.run, self.plan.serve
        self._ensure_model()
        cfg = self._model_arch()
        _, cache_dt = self._serve_dtypes()

        if run.backend != "spmd":
            pre_fn, dec_fn = _ref_serve_steps(cfg, sv.kernel_backend)
            self._serve = {"mode": "ref", "cfg": cfg, "params": self._params,
                           "prefill": jax.jit(pre_fn),
                           "decode": jax.jit(dec_fn),
                           "cache_dt": cache_dt, "mesh": None}
            return

        from repro.configs.base import RunConfig, ShapeConfig
        from repro.core import wave
        from repro.launch.mesh import make_mesh_auto
        from jax.sharding import NamedSharding, PartitionSpec as P

        dsz, ssz, tsz = plan.partition.data, plan.stages, plan.tp
        needed = dsz * ssz * tsz
        if len(jax.devices()) < needed:
            raise RuntimeError(
                f"the spmd serve path needs {needed} devices "
                f"(data*stages*tp = {dsz}*{ssz}*{tsz}) but jax sees "
                f"{len(jax.devices())}; on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={needed} before "
                f"jax initializes")
        mesh = make_mesh_auto((dsz, ssz, tsz), ("data", "stage", "tp"))
        pspecs = lm.param_specs(cfg)
        common = dict(arch=cfg, optimizer=run.optimizer, lr=run.lr,
                      weight_decay=run.weight_decay,
                      compute_dtype=run.compute_dtype,
                      cache_dtype=sv.cache_dtype, overlap=run.overlap,
                      kernel_backend=sv.kernel_backend)
        rc_pre = RunConfig(shape=ShapeConfig("serve_prefill", sv.prompt_len,
                                             sv.max_batch, "prefill"),
                           **common)
        rc_dec = RunConfig(shape=ShapeConfig("serve_decode", sv.max_len,
                                             sv.max_batch, "decode"),
                           **common)
        pre_step, _, _ = wave.build_prefill_step(rc_pre, mesh,
                                                 cache_len=sv.max_len)
        dec_step, _, cspecs = wave.build_decode_step(rc_dec, mesh,
                                                     pos_per_row=True)
        p_sh = self._shard_params(mesh, pspecs, self._params)
        with jax.set_mesh(mesh):
            csh = jax.tree.map(
                lambda s: NamedSharding(mesh, s), cspecs,
                is_leaf=lambda x: isinstance(x, P))

        def pre_fn(params, inputs, cache):
            return pre_step(params, {"inputs": inputs, "cache": cache})

        def dec_fn(params, inputs, cache, pos):
            return dec_step(params, {"inputs": inputs, "cache": cache,
                                     "pos": pos})

        self._serve = {"mode": "spmd", "cfg": cfg, "params": p_sh,
                       "prefill": jax.jit(pre_fn),
                       "decode": jax.jit(dec_fn), "mesh": mesh,
                       "pspecs": pspecs, "cache_sharding": csh,
                       "cache_dt": cache_dt}

    def _ensure_serve_store(self):
        """Build the paged (CacheStore-backed) serve executors: a variable-
        length prefill that scatters K/V pages through the block table, and
        a per-row-position decode over the paged tree. Compiled separately
        from the aligned generate() path (which keeps the contiguous
        reference layout)."""
        if getattr(self, "_serve_paged", None) is not None:
            return
        from repro.serve import cache as cache_lib
        self._ensure_serve()
        plan, run, sv = self.plan, self.plan.run, self.plan.serve
        st = self._serve
        cfg = st["cfg"]
        layout = cache_lib.make_layout(sv.max_batch, sv.max_len,
                                       page_size=sv.page_size,
                                       max_pages=sv.max_pages)

        if st["mode"] != "spmd":
            pre_fn, dec_fn = _ref_paged_steps(cfg, sv.kernel_backend)
            self._serve_paged = {"layout": layout, "shardings": None,
                                 "prefill": jax.jit(pre_fn),
                                 "decode": jax.jit(dec_fn)}
            return

        from repro.configs.base import RunConfig, ShapeConfig
        from repro.core import wave
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = st["mesh"]
        common = dict(arch=cfg, optimizer=run.optimizer, lr=run.lr,
                      weight_decay=run.weight_decay,
                      compute_dtype=run.compute_dtype,
                      cache_dtype=sv.cache_dtype, overlap=run.overlap,
                      kernel_backend=sv.kernel_backend)
        rc_pre = RunConfig(shape=ShapeConfig("serve_prefill", sv.prompt_len,
                                             sv.max_batch, "prefill"),
                           **common)
        rc_dec = RunConfig(shape=ShapeConfig("serve_decode", sv.max_len,
                                             sv.max_batch, "decode"),
                           **common)
        pre_step, _, _ = wave.build_prefill_step(rc_pre, mesh, layout=layout,
                                                 var_len=True)
        dec_step, _, cspecs = wave.build_decode_step(rc_dec, mesh,
                                                     pos_per_row=True,
                                                     layout=layout)
        with jax.set_mesh(mesh):
            shardings = jax.tree.map(
                lambda s: NamedSharding(mesh, s), cspecs,
                is_leaf=lambda x: isinstance(x, P))

        def pre_fn(params, inputs, lens, cache):
            return pre_step(params, {"inputs": inputs, "cache": cache,
                                     "lens": lens})

        def dec_fn(params, inputs, cache, pos):
            return dec_step(params, {"inputs": inputs, "cache": cache,
                                     "pos": pos})

        self._serve_paged = {"layout": layout, "shardings": shardings,
                             "prefill": jax.jit(pre_fn),
                             "decode": jax.jit(dec_fn)}

    def serve_steps(self):
        """(prefill, decode, params): the jitted paged-executor steps the
        Scheduler calls, with the weights they take. For ahead-of-time
        lowering: `prefill.lower(params, prompts, lens,
        store.prefill_input(slots)).compile()` measures compile time and
        exposes the compiled HLO; later calls with the same shapes reuse
        that executable."""
        self._require_serve("serve_steps")
        self._ensure_serve_store()
        pg = self._serve_paged
        return pg["prefill"], pg["decode"], self._serve["params"]

    def serve_store(self):
        """A fresh CacheStore (empty page pool + per-slot state) for this
        Plan's serve shapes, placed for its backend. The Scheduler
        allocates pages at admission and frees them at retirement."""
        from repro.serve import cache as cache_lib
        self._require_serve("serve_store")
        self._ensure_serve_store()
        st, pg = self._serve, self._serve_paged
        return cache_lib.CacheStore(st["cfg"], pg["layout"],
                                    dtype=st["cache_dt"],
                                    shardings=pg["shardings"])

    def serve_cache(self):
        """A blank (all-slots-empty) serve cache for max_batch requests of
        up to serve.max_len positions, placed for this Plan's backend."""
        from repro.models import lm
        self._require_serve("serve_cache")
        self._ensure_serve()
        st, sv = self._serve, self.plan.serve
        cache = lm.init_cache(st["cfg"], sv.max_batch, sv.max_len,
                              dtype=st["cache_dt"])
        if st["mode"] == "spmd":
            cache = jax.device_put(cache, st["cache_sharding"])
        return cache

    def prefill(self, prompts):
        """Prefill a full batch of prompts into a fresh cache.

        prompts: [max_batch, prompt_len] token ids (or [.., .., d_model]
        embeddings for frontend archs). Returns (last_logits [B, vocab],
        cache) — the logits of the final prompt position, i.e. the
        distribution of the first generated token."""
        import jax.numpy as jnp
        self._require_serve("prefill")
        self._ensure_serve()
        st, sv = self._serve, self.plan.serve
        prompts = jnp.asarray(prompts)
        if prompts.shape[:2] != (sv.max_batch, sv.prompt_len):
            raise ValueError(
                f"prompts {prompts.shape} disagree with the frozen serve "
                f"shapes [{sv.max_batch}, {sv.prompt_len}]; pad the batch "
                f"to max_batch (ServeSpec shapes compile once)")
        with self.tracer.span("engine", "prefill", batch=sv.max_batch):
            logits, cache = st["prefill"](st["params"], prompts,
                                          self.serve_cache())
            if self.tracer.enabled:      # span measures compute, not dispatch
                jax.block_until_ready(logits)
        return logits[:, -1], cache

    def prefill_into(self, store, prompts, lens, slots, skip_pages=None):
        """Prefill a batch of (possibly variable-length, right-padded)
        prompts directly into `store`'s page pool.

        prompts [max_batch, prompt_len] token ids with rows 0..len(slots)-1
        carrying real requests; lens [max_batch] per-row prompt lengths;
        slots the store slot assigned to each live row. K/V pages scatter
        through the block table in place; freshly computed per-slot state
        (ring buffers, SSM/RWKV state) is adopted into the assigned slots.
        Returns each live row's last-real-position logits [max_batch,
        vocab].

        skip_pages[j] (optional, per live row) skips *writing* row j's
        first N KV pages: they hold a shared prefix the memory manager
        mapped from the index, already filled with bit-identical K/V.
        The row's compute still spans the whole prompt — per-slot state
        (rings, SSM/RWKV recurrences) is not paged and must be rebuilt
        from position 0 — so prefilling "only the suffix" means only the
        suffix's pages are written; the matched pages' recomputed K/V
        routes to the trash page."""
        import jax.numpy as jnp
        from repro.serve.cache import CacheStore
        self._require_serve("prefill_into")
        self._ensure_serve_store()
        if not isinstance(store, CacheStore):
            raise TypeError(f"prefill_into writes a CacheStore, got "
                            f"{type(store).__name__}")
        st, pg, sv = self._serve, self._serve_paged, self.plan.serve
        prompts = jnp.asarray(prompts)
        if prompts.shape[:2] != (sv.max_batch, sv.prompt_len):
            raise ValueError(
                f"prompts {prompts.shape} disagree with the frozen serve "
                f"shapes [{sv.max_batch}, {sv.prompt_len}] (pad short "
                f"prompts on the right; lens carries the real lengths)")
        lens = jnp.asarray(lens, jnp.int32)
        with self.tracer.span("engine", "prefill", rows=len(slots)):
            logits, out = pg["prefill"](st["params"], prompts, lens,
                                        store.prefill_input(
                                            slots, skip_pages=skip_pages))
            if self.tracer.enabled:
                jax.block_until_ready(logits)
        store.append_rows(out, [(j, s) for j, s in enumerate(slots)])
        return logits[:, -1]

    def decode(self, tokens, cache, pos):
        """One decode position for the whole batch.

        tokens [B, 1] ids (or [B, 1, d] embeddings); pos a scalar (aligned
        batch) or [B] vector (continuous batching: each row at its own
        depth); cache the contiguous tree from prefill() — or a CacheStore,
        which routes through the paged decode step and is updated in
        place. Returns (logits [B, vocab], cache)."""
        import jax.numpy as jnp
        from repro.serve.cache import CacheStore
        self._require_serve("decode")
        sv = self.plan.serve
        pos = jnp.asarray(pos, jnp.int32)
        if pos.ndim == 0:
            # one trace serves both aligned and per-row decode
            pos = jnp.broadcast_to(pos, (sv.max_batch,))
        if isinstance(cache, CacheStore):
            self._ensure_serve_store()
            st, pg = self._serve, self._serve_paged
            with self.tracer.span("engine", "decode"):
                logits, out = pg["decode"](st["params"], jnp.asarray(tokens),
                                           cache.tree, pos)
                if self.tracer.enabled:
                    jax.block_until_ready(logits)
            cache.update(out)
            return logits[:, -1], cache
        self._ensure_serve()
        st = self._serve
        with self.tracer.span("engine", "decode"):
            logits, cache = st["decode"](st["params"], jnp.asarray(tokens),
                                         cache, pos)
            if self.tracer.enabled:
                jax.block_until_ready(logits)
        return logits[:, -1], cache

    def _serve_prompts(self, key):
        """Deterministic synthetic prompts (token ids, or stub embeddings
        for frontend archs) when the caller brings none."""
        import jax.numpy as jnp
        from repro.models import frontend
        sv, cfg = self.plan.serve, self.plan.arch
        if cfg.frontend != "none":
            return frontend.stub_embeddings(cfg, key, sv.max_batch,
                                            sv.prompt_len)
        return jax.random.randint(key, (sv.max_batch, sv.prompt_len), 0,
                                  cfg.vocab_size, dtype=jnp.int32)

    def generate(self, prompts=None, *, callback=None) -> ServeReport:
        """Run the Plan's full serve scenario on one aligned batch: prefill
        max_batch prompts, then gen greedy/sampled decode positions.
        Returns a ServeReport with `tokens` [B, gen]. `callback(step,
        tokens)` is invoked per decode position."""
        import jax.numpy as jnp
        from repro.models import frontend
        self._require_serve("generate")
        self._ensure_serve()
        plan, sv, cfg = self.plan, self.plan.serve, self.plan.arch
        key = jax.random.PRNGKey(sv.sample_seed)
        if prompts is None:
            prompts = self._serve_prompts(key)
        report = ServeReport(arch=cfg.name, backend=plan.run.backend,
                             max_batch=sv.max_batch)
        t_tr = self.tracer.now()
        t_start = time.monotonic()
        logits, cache = self.prefill(prompts)
        jax.block_until_ready(logits)
        report.prefill_s = time.monotonic() - t_start
        report.prefill_calls = 1
        self.tracer.metrics.observe("serve/ttft_s", report.prefill_s)
        tok = _pick(logits, sv.temperature, jax.random.fold_in(key, 0))
        toks = [tok]
        if callback is not None:
            callback(0, tok)
        for t in range(1, sv.gen):
            if cfg.frontend != "none":
                # stub frontends embed generated ids via a fixed projection
                x = frontend.stub_embeddings(cfg, jax.random.fold_in(key, t),
                                             sv.max_batch, 1)
            else:
                x = toks[-1][:, None]
            t0 = time.monotonic()
            logits, cache = self.decode(x, cache,
                                        jnp.int32(sv.prompt_len + t - 1))
            jax.block_until_ready(logits)
            report.decode_s += time.monotonic() - t0
            report.decode_steps += 1
            tok = _pick(logits, sv.temperature, jax.random.fold_in(key, t))
            toks.append(tok)
            if callback is not None:
                callback(t, tok)
        report.tokens = np.stack([np.asarray(t) for t in toks], axis=1)
        report.wall_s = time.monotonic() - t_start
        self.tracer.add_span("engine", "generate", t_tr, self.tracer.now(),
                             gen=sv.gen, batch=sv.max_batch)
        return self.attach_telemetry(report)

    # ------------------------------------------------------------------
    # threads backend: WSP / ASP (policy.execute lands here)
    # ------------------------------------------------------------------
    def _make_worker(self, i: int, wid: str, policy: WSP, *,
                     successor: bool = False) -> VirtualWorker:
        cl = self.plan.cluster
        speeds = cl.speeds or (0.0,) * cl.num_vw
        straggle = cl.straggle_fns or (None,) * cl.num_vw
        injector = self.fault_injector()
        # a rejoined successor does not replay its predecessor's death: the
        # crash / fail_at anchors belong to the original incarnation only
        # (slowdown persists — the *node* is slow, not the process)
        crash_at = None
        if injector is not None and not successor:
            crash_at = injector.crash_wave(i)
        return VirtualWorker(
            wid, self.ps, self._wave_step, self._loader(i, cl.num_vw),
            self._optimizer.init(self.ps.pull()),
            max_waves=self.plan.run.max_waves,
            pull_every=policy.pull_every,
            slowdown=speeds[i], straggle_fn=straggle[i],
            stop_event=self.stop_event,
            fail_at_wave=None if successor else cl.fail_map().get(i),
            async_push=policy.async_push,
            tracer=self.tracer, D=policy.D, tick_plan=self._tick_plan(),
            injector=injector, vw_index=i, crash_at=crash_at,
            gate_timeout_s=self.plan.fault_policy.gate_timeout_s)

    def _fit_threaded(self, policy: WSP, *,
                      rejoin_failed_after: Optional[float] = None,
                      callback: Optional[Callable] = None) -> TrainReport:
        del callback       # per-worker losses are reported at the end
        if self._fleet_ran:
            # a fresh fleet would find the PS clocks already at max_waves
            # and exit with an empty report — fail loudly instead
            raise RuntimeError(
                "this Engine's worker fleet already ran; build a new Engine "
                "(with run.resume=True to continue from a checkpoint)")
        self._fleet_ran = True
        self._ensure_model()
        self._ensure_ps(policy)
        plan, run = self.plan, self.plan.run
        num_vw = plan.cluster.num_vw
        t0 = time.monotonic()
        # register the whole initial fleet before any worker thread runs:
        # a late-registering worker would otherwise start at the already-
        # advanced global clock and silently skip its first waves
        # (VirtualWorker.run's own register() is then an idempotent no-op,
        # since this worker's clock-0 entry pins the global minimum)
        for i in range(num_vw):
            self.ps.register(f"vw{i}")
        for i in range(num_vw):
            wid = f"vw{i}"
            self.workers[wid] = self._make_worker(i, wid, policy)
            self.workers[wid].start()
        ckpt_step = 0
        fpol = plan.fault_policy
        if rejoin_failed_after is not None:
            # the legacy knob, promoted onto the first-class FaultPolicy:
            # rejoin each failed worker once, this many seconds after its
            # eviction was recorded
            import dataclasses as dc
            fpol = dc.replace(fpol, rejoin_delay_s=rejoin_failed_after,
                              rejoin_max=max(1, fpol.rejoin_max))
        supervise = fpol.evict_lag > 0 or fpol.rejoins \
            or plan.faults is not None
        if supervise:
            from repro.faults import FleetSupervisor

            def spawn(i: int, new_wid: str):
                nw = self._make_worker(i, new_wid, policy, successor=True)
                self.workers[new_wid] = nw
                nw.start()
                return nw

            self.supervisor = FleetSupervisor(
                self.ps, self.workers, fpol, spawn=spawn,
                topology=self.topology, tracer=self.tracer)
        periodic = bool(run.ckpt_dir and run.ckpt_every) or supervise
        if not periodic:
            # nothing to supervise: block on the (fixed) worker set directly
            for w in list(self.workers.values()):
                w.join()
        tick = min(0.25, fpol.heartbeat_every_s) if supervise else 0.25
        while periodic and (
                any(w.is_alive() for w in self.workers.values())
                or (self.supervisor is not None
                    and self.supervisor.pending_rejoin())):
            # wake on wave completion / worker exit rather than busy-polling
            self.ps.push_event.wait(timeout=tick)
            self.ps.push_event.clear()
            if self.supervisor is not None:
                self.supervisor.poll()
            # periodic checkpoint (PS + clocks, snapshotted atomically)
            if run.ckpt_dir and run.ckpt_every:
                gc = self.ps.clock.global_clock()
                if gc >= ckpt_step + run.ckpt_every:
                    ckpt_step = gc
                    params, meta = self.ps.checkpoint_state()
                    save_checkpoint(run.ckpt_dir, self._step_offset + gc,
                                    {"params": params}, meta)
        dead = {wid: w.exception for wid, w in self.workers.items()
                if w.exception is not None}
        if dead:
            # not a fault (those are typed and policy-governed below): a
            # worker died of an error, so the run did not do its work
            raise RuntimeError(
                "virtual worker(s) died: " + "; ".join(
                    f"{wid}: {e!r}" for wid, e in dead.items())
            ) from next(iter(dead.values()))
        if run.ckpt_dir and run.ckpt_every:
            # final checkpoint: the loop wakes on push events and may exit
            # the moment the last worker dies, before the last periodic
            # write — resume must still see the end-of-run state
            gc = self.ps.clock.global_clock()
            if gc > ckpt_step:
                params, meta = self.ps.checkpoint_state()
                save_checkpoint(run.ckpt_dir, self._step_offset + gc,
                                {"params": params}, meta)
        report = TrainReport()
        for wid, w in self.workers.items():
            for t, l in zip(w.metrics.wall_clock, w.metrics.losses):
                report.losses.append((t, wid, l))
            report.waves += w.metrics.waves
            report.overlap_seconds += w.metrics.overlap_seconds
            report.push_wait_seconds += w.metrics.push_wait_seconds
            report.gate_timeouts += w.metrics.gate_timeouts
            if w.failed:
                report.crashes += 1
        report.waves_requested = run.max_waves * num_vw
        report.wall_s = time.monotonic() - t0
        report.wait_seconds = dict(self.ps.clock.wait_seconds)
        report.bytes_pushed = self.ps.bytes_pushed
        report.bytes_wire = self.ps.bytes_wire
        report.comm_seconds = self.ps.comm_seconds
        report.comm = self.ps.transport.stats()
        report.late_pushes = self.ps.late_pushes
        report.ps_stalls = self.ps.ps_stalls
        report.drops = report.comm.get("drops", 0)
        report.retries = report.comm.get("retries", 0)
        if self.supervisor is not None:
            report.evictions = [(e.wid, e.at_clock, e.reason, e.rejoined)
                                for e in self.supervisor.evictions]
            report.rejoins = list(self.supervisor.rejoins)
        # fail loudly on silent degradation: a run that timed out at the
        # staleness gate, or lost a worker to a typed fault without a
        # successor taking over, did NOT do the work the Plan requested.
        # FaultPolicy(allow_degraded=True) opts into getting the (counter-
        # annotated) report back instead.
        if not fpol.allow_degraded:
            degraded = []
            for wid, w in self.workers.items():
                if w.metrics.gate_timeouts:
                    degraded.append(f"{wid}: staleness gate timed out")
                elif w.error is not None and (wid + "r") not in self.workers:
                    degraded.append(f"{wid}: {w.error}")
            if degraded:
                from repro.faults import DegradedRunError
                raise DegradedRunError(
                    "run completed degraded (set FaultPolicy.allow_degraded "
                    "to accept): " + "; ".join(degraded), report=report)
        return report

    # ------------------------------------------------------------------
    # threads backend: BSP (policy.execute lands here)
    # ------------------------------------------------------------------
    def _fit_bsp(self, policy: BSP, *,
                 rejoin_failed_after: Optional[float] = None,
                 callback: Optional[Callable] = None) -> TrainReport:
        """Synchronous AllReduce DP: every wave, all VWs' deltas are reduced
        via an emulated ring all-reduce and applied to one global copy.

        Wall clock is a *simulated* straggler-gated time: the VW steps run
        sequentially on this host, so each wave is charged the max over VWs
        of (measured compute + simulated slowdown) plus the topology-
        predicted all-reduce time, and all of a wave's losses share that one
        timestamp."""
        if rejoin_failed_after is not None:
            raise ValueError("elastic rejoin is a parameter-server feature; "
                             "BSP has no PS to rejoin against")
        self._ensure_model()
        plan, run = self.plan, self.plan.run
        num_vw = plan.cluster.num_vw
        topo = plan.cluster.topology
        if isinstance(topo, str):
            topo = make_topology(topo, num_vw)
        self.topology = topo
        names = [f"vw{i}" for i in range(num_vw)]
        loaders = [self._loader(i, num_vw) for i in range(num_vw)]
        params = jax.tree.map(np.asarray, self._params)
        opt_states = [self._optimizer.init(self._params)
                      for _ in range(num_vw)]
        speeds = plan.cluster.speeds or (0.0,) * num_vw
        report = TrainReport()
        waits = {f"vw{i}": 0.0 for i in range(num_vw)}
        tr = self.tracer
        sim_t = 0.0
        for wave_i in range(run.max_waves):
            deltas_all, losses, per_vw_t = [], [], []
            t_wave = 0.0
            with tr.span("engine", "bsp_wave", wave=wave_i):
                for i in range(num_vw):
                    x, y = loaders[i].next()
                    tw0 = time.monotonic()
                    with tr.span(f"vw{i}", "wave", wave=wave_i):
                        deltas, opt_states[i], loss = self._wave_step(
                            params, opt_states[i], x, y)
                    t_i = time.monotonic() - tw0 + speeds[i]
                    per_vw_t.append(t_i)
                    t_wave = max(t_wave, t_i)
                    deltas_all.append(deltas)
                    losses.append(float(loss))
                mean_delta, coll_s = collectives.ring_allreduce(
                    deltas_all, topology=topo, workers=names,
                    average=policy.average)
            # the BSP barrier: each VW waits for the wave's slowest
            for i, t_i in enumerate(per_vw_t):
                waits[f"vw{i}"] += t_wave - t_i
                tr.metrics.observe("train/wait_s", t_wave - t_i,
                                   bounds=SECONDS_BOUNDS)
            params = jax.tree.map(np.add, params, mean_delta)
            nbytes = sum(np.asarray(l).nbytes
                         for l in jax.tree.leaves(mean_delta))
            report.bytes_pushed += nbytes * num_vw
            # ring wire traffic: each VW moves 2(N-1)/N of the vector per wave
            report.bytes_wire += int(2 * (num_vw - 1) * nbytes) \
                if num_vw > 1 else 0
            report.comm_seconds += coll_s
            sim_t += t_wave + coll_s
            for i, l in enumerate(losses):
                report.losses.append((sim_t, f"vw{i}", l))
            report.waves += num_vw
            if callback is not None:
                callback(wave_i, float(np.mean(losses)), t_wave + coll_s)
            self._params = params
            self._bsp_wave += 1
            if run.ckpt_dir and run.ckpt_every and \
                    ((wave_i + 1) % run.ckpt_every == 0
                     or wave_i + 1 == run.max_waves):
                step = self._step_offset + self._bsp_wave
                save_checkpoint(run.ckpt_dir, step, {"params": params},
                                {"wave": step})
        report.wall_s = sim_t
        report.wait_seconds = waits
        self._params = params
        return report

    # ------------------------------------------------------------------
    # spmd backend: the jitted pipelined wave step
    # ------------------------------------------------------------------
    def _ensure_spmd(self):
        if self._spmd is not None:
            return
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.core import wave
        from repro.launch.mesh import make_mesh_auto
        from repro.models import lm

        plan, run = self.plan, self.plan.run
        dsz, ssz, tsz = plan.partition.data, plan.stages, plan.tp
        needed = dsz * ssz * tsz
        if len(jax.devices()) < needed:
            raise RuntimeError(
                f"the spmd backend needs {needed} devices "
                f"(data*stages*tp = {dsz}*{ssz}*{tsz}) but jax sees "
                f"{len(jax.devices())}; on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={needed} before "
                f"jax initializes (launch/train.py --devices does this)")
        mesh = make_mesh_auto((dsz, ssz, tsz), ("data", "stage", "tp"))
        self._ensure_model()               # params for the stage-replaced arch
        arch = self._model_arch()
        pspecs = lm.param_specs(arch)
        shape = plan.shape or ShapeConfig("plan", run.seq, run.batch * dsz,
                                          "train")
        rc = RunConfig(arch=arch, shape=shape, optimizer=run.optimizer,
                       lr=run.lr, weight_decay=run.weight_decay,
                       compute_dtype=run.compute_dtype,
                       loss_chunk=min(run.loss_chunk, run.seq),
                       overlap=run.overlap)
        step, _ = wave.build_train_step(rc, mesh)
        loader = ShardedLoader(self._source, shape.global_batch, run.seq,
                               0, 1)
        p_sh = self._shard_params(mesh, pspecs, self._params)
        with jax.set_mesh(mesh):
            opt_state = self._optimizer.init(p_sh)
        self._spmd = {
            "mesh": mesh, "arch": arch, "loader": loader, "pspecs": pspecs,
            "params": p_sh, "opt_state": opt_state,
            "jstep": jax.jit(step, donate_argnums=(0, 1)), "wave": 0,
        }

    @staticmethod
    def _shard_params(mesh, pspecs, params):
        from jax.sharding import NamedSharding, PartitionSpec as P

        with jax.set_mesh(mesh):
            return jax.device_put(params, jax.tree.map(
                lambda s: NamedSharding(mesh, s), pspecs,
                is_leaf=lambda x: isinstance(x, P)))

    def _spmd_step(self) -> float:
        import jax.numpy as jnp

        st = self._spmd
        x, y = st["loader"].next()
        # the ambient-mesh context is scoped per call rather than held open
        # for the engine's lifetime, so unrelated jax work in this process
        # never runs under a stale mesh
        with jax.set_mesh(st["mesh"]):
            st["params"], st["opt_state"], m = st["jstep"](
                st["params"], st["opt_state"],
                {"inputs": jnp.asarray(x), "labels": jnp.asarray(y)})
        st["wave"] += 1
        return float(m["loss"])

    def _fit_spmd(self, *, callback: Optional[Callable] = None
                  ) -> TrainReport:
        self._ensure_spmd()
        run = self.plan.run
        report = TrainReport()
        tick_plan = self._tick_plan()
        t_start = time.monotonic()
        for w in range(run.max_waves):
            t0 = time.monotonic()
            with self.tracer.span("engine", "wave", wave=w):
                loss = self._spmd_step()
            dt = time.monotonic() - t0
            if tick_plan is not None:
                # the jitted step is opaque to host tracing; render the
                # Plan's pipeline schedule scaled into the measured window
                sched, ticks = tick_plan
                emit_pipeline_ticks(self.tracer, "spmd", sched, ticks,
                                    t0, t0 + dt)
            report.losses.append((time.monotonic() - t_start, "spmd", loss))
            report.waves += 1
            if callback is not None:
                callback(w, loss, dt)
            if run.ckpt_dir and run.ckpt_every and \
                    ((w + 1) % run.ckpt_every == 0
                     or w + 1 == run.max_waves):
                # the final wave checkpoints even off-cadence: resume must
                # see the end-of-run state (matches the threads backend)
                self.save()
        report.wall_s = time.monotonic() - t_start
        # the jitted step has no host-visible sync gate; the key exists so
        # downstream code reads one wait_seconds schema across backends
        report.wait_seconds = {"spmd": 0.0}
        self._params = jax.tree.map(np.asarray, self._spmd["params"])
        return report


# ---------------------------------------------------------------------------
# serve helpers (module level so jit caches don't capture the Engine)
# ---------------------------------------------------------------------------
def _ref_serve_steps(cfg, kernel_backend="ref"):
    """The non-pipelined forward_ref cache path: (prefill_fn, decode_fn),
    each jittable. With kernel_backend="ref" this is the serve correctness
    oracle the pipelined mesh steps (and the Pallas kernel backends) are
    parity-tested against; "interpret"/"tpu" route the attention/SSM mixes
    through repro.kernels."""
    from repro.models import lm

    def pre_fn(params, prompts, cache):
        hid, cache, _ = lm.forward_ref(cfg, params, prompts, mode="prefill",
                                       cache=cache,
                                       kernel_backend=kernel_backend)
        return lm.logits_ref(cfg, params, hid[:, -1:]), cache

    def dec_fn(params, tokens, cache, pos):
        hid, cache, _ = lm.forward_ref(cfg, params, tokens, mode="decode",
                                       cache=cache, pos=pos,
                                       kernel_backend=kernel_backend)
        return lm.logits_ref(cfg, params, hid), cache

    return pre_fn, dec_fn


def _ref_paged_steps(cfg, kernel_backend="ref"):
    """forward_ref over the paged cache tree (threads backend): variable-
    length prefill through the block table + per-row-position decode. With
    a kernel backend the decode walks the block table inside the Pallas
    kernel (no gathered KV view)."""
    import jax.numpy as jnp

    from repro.models import lm

    def pre_fn(params, prompts, lens, cache):
        hid, cache, _ = lm.forward_ref(cfg, params, prompts, mode="prefill",
                                       cache=cache, lens=lens,
                                       kernel_backend=kernel_backend)
        last = jnp.take_along_axis(
            hid, jnp.maximum(lens - 1, 0)[:, None, None], axis=1)
        return lm.logits_ref(cfg, params, last), cache

    def dec_fn(params, tokens, cache, pos):
        hid, cache, _ = lm.forward_ref(cfg, params, tokens, mode="decode",
                                       cache=cache, pos=pos,
                                       kernel_backend=kernel_backend)
        return lm.logits_ref(cfg, params, hid), cache

    return pre_fn, dec_fn


def _pick(logits, temperature, key):
    """Next-token choice over [B, vocab] logits: greedy argmax at
    temperature 0, else categorical sampling."""
    import jax.numpy as jnp

    if temperature == 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits.astype(jnp.float32) / temperature, axis=-1
    ).astype(jnp.int32)
