"""Driver `serve_backlog`: an offline backlog through the program's
continuous-batching Scheduler.

Every request is queued at once (the Scheduler has no arrival times), FIFO,
greedy. The lengths are the quantiles of two clipped lognormals in one
fixed order, so every seed serves the same schedule of work; the seed
draws the token ids and the weights. Set-up ends after `warm_steps` decode steps of the
same run, so both steps are compiled (or loaded from the compile cache)
and the batch is full before the window opens; the window ends at the
first decode step `seconds` later, by StopServing from the run callback.

Token arrivals are read around the Engine's calls (a subclass below): the
first token of a request when its prefill group's logits reach the host,
every later one when its decode step's logits do. Every active slot gets
one token per decode step, so the slot table alone tells the requests
apart; no token is dropped from the count for being still in flight.
A subclass of the Scheduler keeps, for the check, the program's logit of
each token it picks.
"""
from __future__ import annotations

import gc
import time
from statistics import NormalDist

import numpy as np

from bench import common


def quantiles(dist: dict, k: int) -> np.ndarray:
    """The clipped lognormal's k quantiles, ascending."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / k) for i in range(k)])
    v = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(v, dist["min"], dist["max"]).astype(np.int64)


def make_requests(traffic: dict, vocab: int, seed: int):
    """Prompts and output lengths. Each block of `block` requests holds the
    same (prompt, output) pairs: the k-th prompt quantile with a fixed
    output quantile, in an order of its own. The pairing and the orders
    are drawn once from a fixed stream, not from the seed: the Scheduler's
    admissions and retirements follow the lengths alone, so every seed
    serves the same schedule of work. The seed draws the token ids (and
    the weights)."""
    n, k = traffic["requests"], traffic["block"]
    fixed = np.random.default_rng(0)
    pair = fixed.permutation(k)
    p_q, o_q = quantiles(traffic["prompt"], k), quantiles(traffic["output"],
                                                         k)[pair]
    idx = np.concatenate([fixed.permutation(k)
                          for _ in range(-(-n // k))])[:n]
    p_len, o_len = p_q[idx], o_q[idx]
    ids = common.np_rng(seed, 3)
    prompts = [ids.integers(0, vocab, int(k), dtype=np.int32) for k in p_len]
    return prompts, o_len


def plan_for(arch, traffic: dict, cell: dict, kernel_backend: str):
    from repro.api import Plan, ServeSpec
    spec = ServeSpec(prompt_len=traffic["prompt_len"], gen=traffic["gen"],
                     max_batch=cell["max_batch"],
                     page_size=traffic["page_size"],
                     max_pages=cell.get("max_pages", 0),
                     kernel_backend=kernel_backend)
    return Plan(arch=arch, serve=spec)


class Log:
    """Host-clock record of every Engine call of the run."""

    def __init__(self):
        self.prefills = []      # (t0, t1, slots, lens of the live rows)
        self.decodes = []       # (t0, t1, live slots, their lengths)


def timed_engine(Engine):
    """A subclass of the program's Engine that times each serve call to
    the moment its logits are on the host, inside profiler annotations."""
    import jax

    class TimedEngine(Engine):
        log = None

        def prefill_into(self, store, prompts, lens, slots, skip_pages=None):
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation("engine.prefill_into"):
                out = super().prefill_into(store, prompts, lens, slots,
                                           skip_pages=skip_pages)
                out = np.asarray(out)
            n = len(slots)
            self.log.prefills.append((t0, time.monotonic(), list(slots),
                                      np.asarray(lens)[:n].copy()))
            return out

        def decode(self, tokens, cache, pos):
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation("engine.decode"):
                logits, cache = super().decode(tokens, cache, pos)
                jax.block_until_ready(logits)
            with jax.profiler.TraceAnnotation("logits_to_host"):
                logits = np.asarray(logits)
            pos = np.asarray(pos)
            live = np.nonzero(pos > 0)[0]
            self.log.decodes.append((t0, time.monotonic(), live,
                                     pos[live] + 1))
            return logits, cache

    return TimedEngine


def token_times(log: Log, t_end: float):
    """Arrival times of every token, per request occupancy of a slot:
    a list of ascending time lists."""
    events = [(t1, 0, slots) for _, t1, slots, _ in log.prefills]
    events += [(t1, 1, live) for _, t1, live, _ in log.decodes]
    events.sort(key=lambda e: (e[0], e[1]))
    current: dict[int, list] = {}
    done = []
    for t, kind, slots in events:
        if t > t_end:
            break
        for s in slots:
            s = int(s)
            if kind == 0:
                if s in current:
                    done.append(current[s])
                current[s] = [t]
            else:
                current.setdefault(s, []).append(t)
    return done + list(current.values())


def window_stats(log: Log, t0: float, t1: float) -> dict:
    """The window's end-to-end numbers and the counts the per-layer
    readers take, all over (t0, t1]."""
    gaps, held = [], 0
    pre = [(a, b) for a, b, _, _ in log.prefills if b > t0 and a < t1]
    tokens = 0
    for times in token_times(log, t1):
        for k, t in enumerate(times):
            if t0 < t <= t1:
                tokens += 1
                if k:
                    g0 = times[k - 1]
                    gaps.append(t - g0)
                    held += any(g0 < b <= t for _, b in pre)
    dec = [(a, b, live, ln) for a, b, live, ln in log.decodes
           if t0 < b <= t1]
    pf = [(a, b, slots, ln) for a, b, slots, ln in log.prefills
          if t0 < b <= t1]
    calls = sorted((a, b) for a, b, _, _ in dec + pf)
    longest = {"decode": max((b - a for a, b, _, _ in dec), default=0.0),
               "prefill": max((b - a for a, b, _, _ in pf), default=0.0),
               "host": max((c[0] - p[1] for p, c in zip(calls, calls[1:])),
                           default=0.0)}
    return {"window_s": t1 - t0, "tokens": tokens, "gaps": gaps,
            "gaps_with_prefill": held, "decode_calls": dec,
            "prefill_calls": pf, "longest_s": longest}


def run(cfg: dict, traffic: dict, cell: dict, *, seed: int, seconds: float,
        trace: bool, t_start: float, counter, tracer_dir=None,
        fault=None, readings=("program",)) -> dict:
    """One run of a serve cell. Returns the record run.py reduces.
    `fault(engine)` may break the engine before the run (tests only);
    `readings` adds the control's reading ("control", calibration only)."""
    import jax

    from repro.api import Engine
    from repro.api.serving import Request, Scheduler, StopServing
    from repro.models import lm
    from repro.obs import Tracer

    t_driver = time.monotonic()
    arch = common.arch_for(cfg)
    plan = plan_for(arch, traffic, cell, cfg["kernel_backend"])
    prompts, outs = make_requests(traffic, arch.vocab_size, seed)
    params = common.make_params(lm.param_shapes(arch), arch.num_layers, seed)
    jax.block_until_ready(params)
    t_weights = time.monotonic()
    tracer = Tracer() if trace else None
    eng = timed_engine(Engine)(plan, params=params, tracer=tracer)
    eng.log = log = Log()
    if fault is not None:
        fault(eng)
    store = eng.serve_store()
    if any(k in store.tree for k in ("kv_win", "ssm_state", "shift")):
        # the Scheduler copies per-slot state for each admission count;
        # compile those copies now, not in the window
        for n in range(1, plan.serve.max_batch + 1):
            store.append_rows(store.tree, [(j, j) for j in range(n)])
        jax.block_until_ready(store.tree)
    from repro.serve.memory import MemoryManager
    mm = MemoryManager(store, metrics=eng.tracer.metrics)
    reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=int(outs[i]))
            for i in range(len(prompts))]
    length = min(seconds, traffic["trace_seconds"]) if trace else seconds
    st = {"t0": None, "t1": None, "c0": 0, "step0": None}

    def callback(step, active):
        now = log.decodes[-1][1]
        if st["t0"] is None:
            if step < traffic["warm_steps"]:
                return
            if trace:
                common.start_trace(tracer_dir)
            st["t0"], st["c0"], st["step0"] = (log.decodes[-1][1],
                                               counter.compiles, step)
            if trace:       # the window opens once the profiler runs
                st["t0"] = time.monotonic()
            return
        if now - st["t0"] >= length:
            st["t1"] = now
            raise StopServing

    served = {}             # rid -> {k: the program's logit of token k}

    class PickLog(Scheduler):
        def _pick_one(self, row, rid, k, key):
            tok = super()._pick_one(row, rid, k, key)
            served.setdefault(rid, {})[k] = float(row[tok])
            return tok

    report = PickLog(eng).run(reqs, callback=callback, store=store, mm=mm)
    if trace:
        jax.profiler.stop_trace()
    if st["t1"] is None:
        raise RuntimeError("the backlog drained before the window closed; "
                           "give the traffic more requests")
    compiles = counter.compiles - st["c0"]
    dev = common.device_record(jax.devices())
    w = window_stats(log, st["t0"], st["t1"])
    events = [e for e in (tracer.events() if tracer else [])
              if st["t0"] <= e[3] <= st["t1"]]
    finished = [{"rid": r.rid, "prompt": prompts[r.rid],
                 "tokens": list(r.tokens),
                 "logits": [served[r.rid][k] for k in range(len(r.tokens))]}
                for r in report.requests if not (r.failed or r.shed)]
    attempted = len(finished)
    failed = sum(1 for r in report.requests if r.failed or r.shed)
    bad_len = [r["rid"] for r in finished
               if len(r["tokens"]) != int(outs[r["rid"]])]
    sv = plan.serve
    page_pool = store.pages_total > 0
    width = np.dtype(store.dtype).itemsize
    del eng, store, mm, report, reqs
    gc.collect()

    from bench.reference import serve_check
    t_check = time.monotonic()
    picked = serve_check.sample(finished, common.np_rng(seed, 4),
                                **traffic["check"])
    check = serve_check.compare(cfg, params, picked, sv.prompt_len + sv.gen)
    extra = {}
    if "control" in readings:
        extra["control"] = serve_check.compare(
            cfg, params, picked, sv.prompt_len + sv.gen, control=True)
    check_s = time.monotonic() - t_check
    return {
        "kind": "serve", "window": w, "t0": st["t0"], "t1": st["t1"],
        "setup_s": st["t0"] - t_start, "compiles_in_window": compiles,
        "device": dev, "events": events,
        "attempted": attempted, "failed": failed + len(bad_len),
        "max_batch": sv.max_batch, "page_size": sv.page_size,
        "page_pool": page_pool, "width": width, "finished": finished,
        "readings": extra,
        "cfg": cfg, "arch": arch,
        "extra_lines": {
            "setup_split_s": {
                "imports": t_driver - t_start,
                "weights": t_weights - t_driver,
                "first_prefill": log.prefills[0][1] - t_weights,
                "first_decode": log.decodes[0][1] - log.prefills[0][1],
                "warm_steps": st["t0"] - log.decodes[0][1]},
            "gaps": len(w["gaps"]),
            "gaps_with_prefill_share": w["gaps_with_prefill"]
            / max(len(w["gaps"]), 1),
            "decode_calls": len(w["decode_calls"]),
            "prefill_calls": len(w["prefill_calls"]),
            "tokens": w["tokens"], "longest_s": w["longest_s"],
            "check_s": check_s},
        "check": check,
        "metrics": {
            "serve_tokens_per_s": w["tokens"] / w["window_s"],
            "itl_p95_ms": 1e3 * common.quantile(w["gaps"], 0.95),
            "setup_s": st["t0"] - t_start,
        },
    }
