"""The observability layer (repro.obs): tracer/metrics semantics, the
zero-overhead disabled path, Chrome-trace export schema, the WSP staleness
audit, scheduler event invariants and Telemetry report plumbing."""
import glob
import json
import threading
import time

import numpy as np
import pytest

from repro.api import (BSP, ClusterSpec, Engine, Plan, RunSpec, ServeReport,
                       ServeSpec, Telemetry, WSP, get_preset)
from repro.api.serving import Request, Scheduler
from repro.configs import ARCHS, reduced
from repro.core.wave import tick_schedule
from repro.core.param_server import ParameterServer
from repro.obs import (NULL_SPAN, NULL_TRACER, Histogram, MetricsRegistry,
                       Tracer, current_tracer, emit_pipeline_ticks)
from repro.obs import trace as trace_mod
from repro.obs.export import load, to_chrome, validate_chrome, write_chrome
from repro.obs.metrics import INT_BOUNDS, quantile_from_snapshot
from repro.obs.summary import main as summary_main, summarize


def _cfg(**over):
    base = dict(num_layers=2, d_model=32, d_ff=64, vocab_size=256,
                num_microbatches=2)
    base.update(over)
    return reduced(ARCHS["qwen3-0.6b"], **base)


def _wsp_plan(**over):
    kw = dict(arch=_cfg(),
              cluster=ClusterSpec(num_vw=2, topology="2node"),
              sync=WSP(D=1),
              run=RunSpec(max_waves=3, batch=4, seq=16))
    kw.update(over)
    return Plan(**kw)


# ---------------------------------------------------------------------------
# metrics: histogram + registry semantics
# ---------------------------------------------------------------------------
def test_histogram_buckets_and_exact_sidecars():
    h = Histogram(bounds=(1, 2, 4))
    for v in (0, 1, 1.5, 3, 100):
        h.observe(v)
    assert h.counts == [2, 1, 1, 1]          # last = overflow
    assert h.count == 5 and h.vmin == 0 and h.vmax == 100
    assert h.total == pytest.approx(105.5)
    # quantiles resolve to bucket upper edges; overflow to the exact max
    assert h.quantile(0.1) == 1
    assert h.quantile(0.5) == 2           # 3rd of 5 samples sits in (1, 2]
    assert h.quantile(0.99) == 100
    snap = h.snapshot()
    assert quantile_from_snapshot(snap, 0.5) == h.quantile(0.5)
    assert quantile_from_snapshot(snap, 0.99) == 100
    assert quantile_from_snapshot({}, 0.5) is None
    assert quantile_from_snapshot(None, 0.5) is None


def test_registry_roundtrip_and_disabled_noop():
    m = MetricsRegistry()
    m.counter_inc("a")
    m.counter_inc("a", 2.0)
    m.gauge_set("g", 7)
    m.observe("h", 3, bounds=INT_BOUNDS)
    snap = m.snapshot()
    assert snap["counters"]["a"] == 3.0
    assert snap["gauges"]["g"] == 7.0
    assert snap["histograms"]["h"]["count"] == 1
    off = MetricsRegistry(enabled=False)
    off.counter_inc("a")
    off.gauge_set("g", 1)
    off.observe("h", 1)
    assert off.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


# ---------------------------------------------------------------------------
# tracer: disabled is a true no-op; enabled records typed events
# ---------------------------------------------------------------------------
def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    # span() hands back the shared singleton: no per-call allocation
    assert tr.span("t", "x") is NULL_SPAN
    assert NULL_TRACER.span("t", "y", a=1) is NULL_SPAN
    with tr.span("t", "x"):
        pass
    tr.add_span("t", "x", 0.0, 1.0)
    tr.instant("t", "x")
    tr.counter("t", "c", 3)
    tr.metrics.observe("h", 1)
    assert len(tr) == 0
    assert tr.metrics.snapshot() == {"counters": {}, "gauges": {},
                                     "histograms": {}}


def test_disabled_tracer_hot_path_overhead():
    """The disabled hot path must cost no measurable per-wave time: 100k
    span() calls in well under a second (they are a flag check + singleton
    return). A generous absolute bound keeps this robust on slow CI."""
    tr = Tracer(enabled=False)
    t0 = time.monotonic()
    for _ in range(100_000):
        with tr.span("track", "name", k=1):
            with current_tracer().span("track", "inner"):
                pass
        tr.counter("track", "c", 1)
    assert time.monotonic() - t0 < 1.0
    assert len(tr) == 0
    assert current_tracer() is NULL_TRACER


def test_tracer_records_span_instant_counter():
    t = {"v": 0.0}

    def clk():
        t["v"] += 1.0
        return t["v"]

    tr = Tracer(clock=clk)
    with tr.span("trk", "work", tag="x"):
        pass
    tr.instant("trk", "mark", n=1)
    tr.counter("trk", "depth", 4)
    evs = tr.events()
    assert [e[0] for e in evs] == ["X", "i", "C"]
    ph, track, name, t0, dur, args = evs[0]
    assert (track, name, t0, dur, args) == ("trk", "work", 1.0, 1.0,
                                            {"tag": "x"})
    assert evs[2][5] == {"depth": 4}


# ---------------------------------------------------------------------------
# the enclosing span: parent, current_tracer(), the profiler's clock
# ---------------------------------------------------------------------------
def test_nested_spans_record_their_parent():
    tr = Tracer()
    with tr.span("a", "outer", k=1):
        with tr.span("b", "inner"):
            pass
        with tr.scoped("r0/").span("c", "leaf"):
            pass
    with tr.span("a", "again"):
        pass
    by = {e[2]: e[5] for e in tr.events()}
    assert by["outer"] == {"k": 1} and by["again"] == {}
    assert by["inner"] == {"parent": "a/outer"}
    assert by["leaf"] == {"parent": "a/outer"}
    with tr.scoped("r1/").span("sched", "step"):
        with tr.scoped("r1/").span("sched", "decode_step"):
            pass
    assert tr.events()[-2][5] == {"parent": "r1/sched/step"}


def test_current_tracer_is_the_enclosing_spans_per_thread():
    tr = Tracer()
    view = tr.scoped("r0/")
    seen = {}

    def other():
        seen["other"] = current_tracer()

    assert current_tracer() is NULL_TRACER
    with tr.span("t", "outer"):
        assert current_tracer() is tr
        with view.span("t", "inner"):
            assert current_tracer() is view    # the view keeps its prefix
            with current_tracer().span("t", "leaf"):
                pass
            th = threading.Thread(target=other)
            th.start()
            th.join(timeout=10)
            assert not th.is_alive()
        assert current_tracer() is tr
    assert current_tracer() is NULL_TRACER
    assert seen["other"] is NULL_TRACER
    leaf = [e for e in tr.events() if e[2] == "leaf"][0]
    assert leaf[1] == "r0/t" and leaf[5] == {"parent": "r0/t/inner"}


def test_disabled_tracer_grows_no_stack():
    tr = Tracer(enabled=False)
    with tr.span("t", "x"):
        assert current_tracer() is NULL_TRACER
        assert trace_mod._open.stack == []
        with Tracer().span("t", "y"):
            # an enabled span inside a disabled one has no parent
            assert len(trace_mod._open.stack) == 1
    assert trace_mod._open.stack == []
    assert len(tr) == 0


def test_enabled_span_is_on_the_profilers_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("probe_track", "probe_span"):
            time.sleep(0.02)
        tr.add_span("probe_track", "after_the_fact", 0.0, 1.0)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [e for plane in ProfileData.from_file(path[0]).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith("probe_track")]
    assert [e.name for e in host] == ["probe_track/probe_span"]
    dur = [e[4] for e in tr.events() if e[2] == "probe_span"][0]
    assert abs(host[0].duration_ns * 1e-9 - dur) < 1e-3


def test_ps_lock_waits_are_recorded_only_when_traced():
    import numpy as np
    tree = {"a": np.zeros(4, np.float32), "b": np.ones(3, np.float32)}
    plain = ParameterServer(tree, num_shards=2)
    lock = plain._locks[0]
    assert plain._locked(lock, "shard") is lock     # untraced: the lock
    tr = Tracer()
    ps = ParameterServer(tree, num_shards=2, tracer=tr)
    held = threading.Event()

    def hold():
        with ps._locks[0]:
            held.set()
            time.sleep(0.05)

    th = threading.Thread(target=hold)
    th.start()
    held.wait(10)
    ps.pull("vw0")
    th.join(timeout=10)
    assert not th.is_alive()
    waits = [e for e in tr.events() if e[1:3] == ("ps", "lock_wait")]
    assert len(waits) == 1
    assert waits[0][5] == {"lock": "shard"} and waits[0][4] > 0.02
    ps.push_wave("vw0", {"a": np.ones(4, np.float32),
                         "b": np.ones(3, np.float32)})
    # nobody held a lock: no wait long enough to record
    assert len([e for e in tr.events() if e[2] == "lock_wait"]) == 1
    flat = [e for e in tr.events() if e[1:3] == ("ps", "push_flatten")]
    assert len(flat) == 1 and flat[0][5] == {"wid": "vw0"}


# ---------------------------------------------------------------------------
# pipeline tick rendering
# ---------------------------------------------------------------------------
def test_tick_schedule_shapes():
    sched, ticks = tick_schedule(2, 2)
    assert ticks == 3                     # nm + (stages-1), skew 1
    assert len(sched) == 2 * 3
    for s in range(2):
        mbs = [mb for st, _, mb in sched if st == s and mb >= 0]
        assert mbs == [0, 1]              # every stage runs every microbatch
    _, ticks_ov = tick_schedule(3, 4, overlap=True)
    assert ticks_ov == 4 + 2 * 2          # skew 2 under overlap


def test_emit_pipeline_ticks_spans_and_bubble_fraction():
    tr = Tracer(clock=lambda: 0.0)
    sched, ticks = tick_schedule(2, 2)
    emit_pipeline_ticks(tr, "vw0", sched, ticks, 0.0, 3.0)
    evs = tr.events()
    assert len(evs) == 2 * 3              # one span per (stage, tick)
    assert {e[1] for e in evs} == {"vw0/stage0", "vw0/stage1"}
    bubbles = [e for e in evs if e[2] == "bubble"]
    assert len(bubbles) == 2              # 1 bubble tick per stage
    snap = tr.metrics.snapshot()["counters"]
    assert snap["pipe/busy_s"] == pytest.approx(4.0)
    assert snap["pipe/bubble_s"] == pytest.approx(2.0)
    # disabled: no events, no counters
    emit_pipeline_ticks(NULL_TRACER, "vw0", sched, ticks, 0.0, 3.0)
    assert len(NULL_TRACER) == 0


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------
def _sample_tracer():
    t = {"v": 10.0}

    def clk():
        t["v"] += 0.5
        return t["v"]

    tr = Tracer(clock=clk)
    with tr.span("alpha", "work"):
        tr.instant("beta", "mark")
    tr.counter("alpha", "depth", 2)
    return tr


def test_export_chrome_schema(tmp_path):
    tr = _sample_tracer()
    doc = to_chrome(tr.events(), telemetry=tr.metrics.snapshot())
    validate_chrome(doc)
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M" and evs[0]["name"] == "process_name"
    # every track got thread_name metadata; tids are stable per track
    names = {e["args"]["name"]: e["tid"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert set(names) == {"alpha", "beta"}
    xs = [e for e in evs if e["ph"] == "X"]
    ins = [e for e in evs if e["ph"] == "i"]
    assert len(xs) == 1 and len(ins) == 1
    assert xs[0]["tid"] == names["alpha"] and xs[0]["dur"] > 0
    assert ins[0]["s"] == "t"
    # timestamps are µs relative to the earliest event
    assert min(e["ts"] for e in evs if e["ph"] != "M") == 0
    p = tmp_path / "t.json"
    assert write_chrome(tr.events(), str(p)) == str(p)
    assert load(str(p))["traceEvents"]
    json.loads(p.read_text())             # plain JSON on disk


def test_validate_chrome_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome({})
    with pytest.raises(ValueError, match="empty"):
        validate_chrome({"traceEvents": []})
    tr = _sample_tracer()
    doc = to_chrome(tr.events())
    bad = json.loads(json.dumps(doc))
    bad["traceEvents"][1]["ph"] = "Z"
    with pytest.raises(ValueError, match="ph"):
        validate_chrome(bad)
    bad2 = json.loads(json.dumps(doc))
    for e in bad2["traceEvents"]:
        if e["ph"] == "X":
            e["ts"] = -5
    with pytest.raises(ValueError, match="ts"):
        validate_chrome(bad2)


def test_summary_cli_exit_codes(tmp_path):
    tr = _sample_tracer()
    p = tmp_path / "ok.json"
    tr.export(str(p))
    assert summary_main([str(p)]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert summary_main([str(bad)]) == 1
    assert summary_main([str(tmp_path / "missing.json")]) == 1


# ---------------------------------------------------------------------------
# instrumented training: bit-identity, staleness audit, wait_seconds
# ---------------------------------------------------------------------------
def test_fit_bit_identical_with_and_without_tracer():
    """Tracing must observe, never perturb: loss sequences are bit-identical
    between an untraced and a traced engine. Deterministic configs only —
    a single-VW WSP fleet and the sequential BSP loop; multi-VW WSP loss
    streams depend on thread interleaving with or without tracing."""
    def solo():
        return _wsp_plan(cluster=ClusterSpec(num_vw=1), sync=WSP(D=0))
    plain = Engine(solo()).fit()
    tr = Tracer()
    traced = Engine(solo(), tracer=tr).fit()
    a, b = plain.losses_by_worker(), traced.losses_by_worker()
    assert a.keys() == b.keys()
    for wid in a:
        assert a[wid] == b[wid]           # exact float equality
    assert plain.telemetry is None
    assert traced.telemetry is not None
    assert len(tr) > 0

    def bsp():
        return Plan(arch=_cfg(), cluster=ClusterSpec(num_vw=2,
                                                     topology="2node"),
                    sync=BSP(), run=RunSpec(max_waves=2, batch=4, seq=16))
    p = Engine(bsp()).fit()
    t = Engine(bsp(), tracer=Tracer()).fit()
    assert p.losses_by_worker() == t.losses_by_worker()


def test_generate_bit_identical_with_and_without_tracer():
    def plan():
        return Plan(arch=_cfg(), run=RunSpec(),
                    serve=ServeSpec(prompt_len=8, gen=4, max_batch=2))
    plain = Engine(plan()).generate()
    traced = Engine(plan(), tracer=Tracer()).generate()
    np.testing.assert_array_equal(np.asarray(plain.tokens),
                                  np.asarray(traced.tokens))
    assert plain.telemetry is None
    assert traced.telemetry is not None
    assert traced.prefill_calls == 1


def test_traced_wsp_staleness_audited_against_D(tmp_path):
    tr = Tracer()
    plan = _wsp_plan(cluster=ClusterSpec(num_vw=2, topology="2node",
                                         speeds=(0.0, 0.05)),
                     sync=WSP(D=2, pull_every=2, async_push=True),
                     run=RunSpec(max_waves=4, batch=4, seq=16))
    rep = Engine(plan, tracer=tr).fit()
    tel = rep.telemetry
    st = tel.histograms["wsp/staleness"]
    assert st["count"] >= plan.run.max_waves     # one sample per wave per VW
    assert st["max"] <= 2                        # the gate's guarantee
    assert tel.gauges["wsp/D"] == 2
    assert "wsp/staleness_violations" not in tel.counters
    assert tel.bubble_fraction() == pytest.approx(1 / 3)   # 2 stages, 2 mb
    assert any(k.startswith("link/") for k in tel.gauges)
    # the summary CLI performs the same audit on the exported trace
    p = tmp_path / "wsp.json"
    tr.export(str(p))
    lines = summarize(load(str(p)))
    assert any("bound D=2 -> OK" in ln for ln in lines)
    # a doctored trace whose measured max exceeds D must fail the audit
    doc = load(str(p))
    doc["telemetry"]["histograms"]["wsp/staleness"]["max"] = 3
    with pytest.raises(ValueError, match="staleness audit failed"):
        summarize(doc)


def test_wait_seconds_normalized_across_backends():
    # threads: wid -> gate-blocked seconds for every worker
    rep = Engine(_wsp_plan()).fit()
    assert sorted(rep.wait_seconds) == ["vw0", "vw1"]
    # bsp: wid -> straggler wait; the slowed worker waits least
    bsp = Plan(arch=_cfg(),
               cluster=ClusterSpec(num_vw=2, topology="2node",
                                   speeds=(0.0, 0.05)),
               sync=BSP(), run=RunSpec(max_waves=2, batch=4, seq=16))
    rep = Engine(bsp).fit()
    assert sorted(rep.wait_seconds) == ["vw0", "vw1"]
    assert all(v >= 0 for v in rep.wait_seconds.values())
    # the barrier charges somebody: with asymmetric speeds the faster
    # worker waits (direction is not asserted — first-call jit compile
    # can land on either worker's measured wave time)
    assert max(rep.wait_seconds.values()) > 0
    # spmd: the jitted step has no host-visible gate, but the key exists
    rep = Engine(get_preset("spmd_tiny").replace(run__max_waves=1)).fit()
    assert rep.wait_seconds == {"spmd": 0.0}


# ---------------------------------------------------------------------------
# scheduler: event invariants, admission groups, TTFT
# ---------------------------------------------------------------------------
def _sched_run(n_requests=6, tracer=None):
    plan = Plan(arch=_cfg(),
                serve=ServeSpec(prompt_len=8, gen=3, max_batch=2,
                                page_size=4),
                run=RunSpec())
    eng = Engine(plan, tracer=tracer) if tracer else Engine(plan)
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, 256, size=int(rng.integers(2, 9)),
                                        dtype=np.int32).astype(np.int32))
            for i in range(n_requests)]
    return Scheduler(eng).run(reqs), eng


def test_scheduler_event_invariants():
    tr = Tracer()
    rep, _ = _sched_run(tracer=tr)
    evs = tr.events()
    admits = [e for e in evs if e[0] == "i" and e[2] == "admit"]
    retires = [e for e in evs if e[0] == "i" and e[2] == "retire"]
    # every admitted request retires (run drains the queue); rids match 1:1
    assert sorted(e[5]["rid"] for e in admits) == list(range(6))
    assert sorted(e[5]["rid"] for e in retires) == list(range(6))
    # decode-step slot counts reconcile with the report
    steps = [e for e in evs if e[0] == "X" and e[2] == "decode_step"]
    assert len(steps) == rep.decode_steps
    assert sum(e[5]["slots"] for e in steps) == rep.slot_steps
    # prefill groups: one span per batched prefill call
    groups = [e for e in evs if e[0] == "X" and e[2] == "prefill_group"]
    assert len(groups) == rep.prefill_calls
    assert {e[5]["group"] for e in groups} == \
        {e[5]["group"] for e in admits}


def test_scheduler_groups_and_ttft():
    rep, _ = _sched_run()
    assert rep.prefill_calls >= 2          # 6 requests through 2 slots
    by_group: dict = {}
    for r in rep.requests:
        assert 0 <= r.group < rep.prefill_calls
        assert r.ttft_s > 0
        by_group.setdefault(r.group, []).append(r)
    # an admission group shares one prefill cost and one TTFT
    for rs in by_group.values():
        assert len({r.prefill_s for r in rs}) == 1
        assert len({r.ttft_s for r in rs}) == 1
    # group-attributed cost: mean_ttft uses each group's cost once, so it
    # never exceeds the run's wall clock (summing per-request prefill_s
    # over co-batched requests would)
    assert rep.mean_ttft() <= rep.wall_s
    assert sum({r.group: r.prefill_s for r in rep.requests}.values()) == \
        pytest.approx(rep.prefill_s)
    # later groups admit later, so TTFT grows with the group id
    ttfts = {r.group: r.ttft_s for r in rep.requests}
    ordered = [ttfts[g] for g in sorted(ttfts)]
    assert ordered == sorted(ordered)
    assert ServeReport().mean_ttft() is None


def test_telemetry_helpers():
    m = MetricsRegistry()
    m.observe("wsp/staleness", 1, bounds=INT_BOUNDS)
    m.observe("wsp/staleness", 2, bounds=INT_BOUNDS)
    m.counter_inc("pipe/busy_s", 3.0)
    m.counter_inc("pipe/bubble_s", 1.0)
    m.gauge_set("link/eth/bytes", 1e6)
    m.gauge_set("link/eth/modeled_s", 0.5)
    tel = Telemetry.from_metrics(m)
    assert tel.staleness_max() == 2
    assert tel.hist_quantile("wsp/staleness", 0.5) == 1
    assert tel.bubble_fraction() == pytest.approx(0.25)
    assert tel.link_utilization(1.0) == {"eth": 0.5}
    assert tel.to_dict()["gauges"]["link/eth/bytes"] == 1e6
    assert Telemetry().staleness_max() is None
    assert Telemetry().bubble_fraction() is None


# ---------------------------------------------------------------------------
# spans where the work happens: the locked wave step, the parameter server,
# the Scheduler's loop, the Engine's decode dispatch
# ---------------------------------------------------------------------------
#: the benchmark harness's own profiler annotations (bench/trace_reduce.py's
#: ANNOTATIONS); a program span named like one would move its idle labels
HARNESS_ANNOTATIONS = {"engine.prefill_into", "engine.decode",
                       "logits_to_host", "wave_step", "ps.pull",
                       "ps.push_wave", "loader.next"}


@pytest.fixture(scope="module")
def traced_fit_events():
    tr = Tracer()
    rep = Engine(_wsp_plan(), tracer=tr).fit()
    return tr.events(), rep


@pytest.fixture(scope="module")
def traced_sched_events():
    tr = Tracer()
    rep, _ = _sched_run(tracer=tr)
    return tr.events(), rep


def test_threaded_fit_spans_every_wave(traced_fit_events):
    evs, rep = traced_fit_events
    waves = [e for e in evs if e[0] == "X" and e[2] == "wave"]
    assert len(waves) == sum(len(v) for v in
                             rep.losses_by_worker().values()) > 0
    parts = ("lock_wait", "to_device", "compute", "to_host")
    for part in parts:
        spans = [e for e in evs if e[1:3] == ("wave_step", part)]
        assert len(spans) == len(waves), part
        assert {e[5]["parent"] for e in spans} == {"vw0/wave", "vw1/wave"}
    # within each wave span the four parts run in order
    for w in waves:
        inside = sorted((e[3], e[2]) for e in evs if e[1] == "wave_step"
                        and w[3] <= e[3] <= w[3] + w[4]
                        and e[5]["parent"] == f"{w[1]}/wave")
        assert [n for _, n in inside] == list(parts)
    flat = [e for e in evs if e[1:3] == ("ps", "push_flatten")]
    pushes = [e for e in evs if e[0] == "X" and e[2] == "push"]
    assert len(flat) == len(pushes) == len(waves)
    assert {e[5]["parent"] for e in flat} == {"vw0/push", "vw1/push"}
    apply = [e for e in evs if e[1:3] == ("ps", "push_apply")]
    assert {e[5]["parent"] for e in apply} == {"vw0/push", "vw1/push"}


def test_sched_step_spans_hold_their_children(traced_sched_events):
    evs, rep = traced_sched_events
    steps = [e for e in evs if e[1:3] == ("sched", "step")]
    kids = [e for e in evs
            if e[0] == "X" and e[2] in ("prefill_group", "decode_step")]
    assert len(kids) == rep.prefill_calls + rep.decode_steps
    assert len(steps) >= rep.decode_steps
    for k in kids:
        assert k[5]["parent"] == "sched/step"
        assert any(s[3] <= k[3] and k[3] + k[4] <= s[3] + s[4]
                   for s in steps)
    dispatch = [e for e in evs if e[1:3] == ("engine", "decode_dispatch")]
    decode = [e for e in evs if e[1:3] == ("engine", "decode")]
    assert len(dispatch) == len(decode) == rep.decode_steps
    assert {e[5]["parent"] for e in dispatch} == {"engine/decode"}
    assert {e[5]["parent"] for e in decode} == {"sched/decode_step"}


def test_no_program_span_is_named_like_a_harness_annotation(
        traced_fit_events, traced_sched_events):
    labels = {f"{e[1]}/{e[2]}"
              for evs, _ in (traced_fit_events, traced_sched_events)
              for e in evs if e[0] == "X"}
    assert {"vw0/push", "ps/push_apply", "wave_step/to_host", "sched/step",
            "engine/decode_dispatch"} <= labels
    assert not labels & HARNESS_ANNOTATIONS
