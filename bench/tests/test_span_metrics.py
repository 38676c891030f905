"""The readers of the program's own spans (repro.obs Tracer events in the
run's record) on hand-made records: each reads its number, and nothing
where the program records no such span, as an older commit does not.

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import os

import pytest

from bench import common
from bench import run as bench_run

SPEC = common.benchmark_spec()


def read(metric, rec):
    return bench_run.load_module(os.path.join(
        common.BENCH, "metrics", metric + ".py")).read(rec)


def X(track, name, t0, dur, **args):
    return ("X", track, name, t0, dur, args)


#: three waves pushed (vw0 waves 0 and 1, vw1 wave 0); a pull after each
PARENT_TRAIN = [X("vw0", "push", 0.0, 1.0, wave=0),
                X("vw1", "push", 2.0, 1.0, wave=0),
                X("vw0", "push", 4.0, 1.0, wave=1),
                X("vw0", "pull", 5.0, 0.2, wave=1)] + [
    X("ps", "push_apply", t, 0.3, wid="vw0", parent="vw0/push")
    for t in (0.5, 2.5, 4.5)] + [
    X("ps", "pull_serve", t, 0.1, wid="vw0") for t in (1.1, 3.1, 5.1)]

TRAIN = PARENT_TRAIN + [
    X("ps", "push_flatten", t, 0.2, wid="vw0") for t in (0.1, 2.1, 4.1)] + [
    X("ps", "lock_wait", t, 0.05, lock="shard") for t in (0.6, 2.6)] + [
    X("wave_step", part, t, dur, parent="vw0/wave")
    for part, dur in (("lock_wait", 0.4), ("to_device", 0.15),
                      ("to_host", 0.25))
    for t in (6.0, 7.0, 8.0)] + [
    X("wave_step", "compute", 9.0, 0.7, parent="vw0/wave"),
    ("i", "vw0", "push_enqueue", 9.5, 0.0, {}),
    X("vw0", "wave", 9.0, 1.0, wave=2)]

SERVE = [X("sched", "step", 0.0, 1.0, step=0),
         X("sched", "prefill_group", 0.1, 0.5, parent="sched/step"),
         X("sched", "decode_step", 0.6, 0.35, parent="sched/step"),
         X("engine", "decode", 0.61, 0.33, parent="sched/decode_step"),
         X("engine", "decode_dispatch", 0.61, 0.002, parent="engine/decode"),
         X("sched", "step", 1.0, 0.5, step=1),
         X("sched", "decode_step", 1.05, 0.4, parent="sched/step"),
         X("engine", "decode", 1.06, 0.38, parent="sched/decode_step"),
         X("engine", "decode_dispatch", 1.06, 0.004, parent="engine/decode"),
         # the window opened inside a pass: its child has no step here
         X("sched", "decode_step", 2.05, 0.4, parent="sched/step")]

EXPECTED = {
    "ps_push_flatten_ms.train": 200.0,
    "ps_push_apply_ms.train": 300.0,
    "ps_pull_copy_ms.train": 100.0,
    "ps_lock_wait_ms.train": 100.0 / 3,
    "wave_lock_wait_ms.train": 400.0,
    "wave_to_device_ms.train": 150.0,
    "wave_to_host_ms.train": 250.0,
    "sched_host_ms.serve": 1e3 * ((1.0 - 0.85) + (0.5 - 0.4)) / 2,
    "decode_dispatch_ms.serve": 3.0,
}


def record(metric, events):
    return {"kind": "train" if metric.endswith(".train") else "serve",
            "events": events}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reads_its_spans(metric):
    events = TRAIN if metric.endswith(".train") else SERVE
    assert read(metric, record(metric, events)) == pytest.approx(
        EXPECTED[metric])
    other = "serve" if metric.endswith(".train") else "train"
    assert read(metric, {"kind": other, "events": events}) is None


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reads_nothing_without_its_spans(metric):
    assert read(metric, record(metric, [])) is None
    if metric.endswith(".train"):
        parent = read(metric, record(metric, PARENT_TRAIN))
        if metric in ("ps_push_apply_ms.train", "ps_pull_copy_ms.train"):
            assert parent == pytest.approx(EXPECTED[metric])  # older spans
        else:
            assert parent is None
    else:
        # an older commit: the Scheduler's and the Engine's other spans
        older = [e for e in SERVE if e[2] not in ("step", "decode_dispatch")]
        assert read(metric, record(metric, older)) is None


def test_lock_waits_read_zero_where_the_program_records_them():
    quiet = [e for e in TRAIN if e[1:3] != ("ps", "lock_wait")]
    assert read("ps_lock_wait_ms.train",
                record("ps_lock_wait_ms.train", quiet)) == 0.0


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_each_reads_in_one_cell(metric):
    entry = next(m for m in SPEC["per_layer"] if m["name"] == metric)
    assert entry["source"] == "program_span" and entry["unit"] == "ms"
    cell = ("qwen3-0.6b.train.wsp2" if metric.endswith(".train")
            else "qwen3-0.6b.serve.chat")
    assert entry["workloads"] == [cell]
    assert entry["moves"] == ("train_tokens_per_s" if metric.endswith(
        ".train") else "serve_tokens_per_s")
