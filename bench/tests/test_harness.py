"""The harness finds every part of a cell by name, and the trace reduction
computes busy time, idle gaps and their labels as it says.

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import json
import os
import re

import numpy as np
import pytest

from bench import common, trace_reduce
from bench import run as bench_run

SPEC = common.benchmark_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_part_of_a_cell_is_found_by_name(w):
    _, cfg, traffic, cell = common.cell_files(w["name"], SPEC)
    assert cfg["name"] == w["config"]
    assert os.path.exists(os.path.join(common.BENCH, "drivers",
                                       traffic["driver"] + ".py"))
    e2e, layer = bench_run.cell_metrics(SPEC, w["name"])
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer
    for m in layer:
        assert os.path.exists(os.path.join(common.BENCH, "metrics",
                                           m["name"] + ".py"))
    if traffic["driver"] == "serve_backlog":
        assert cell["max_batch"] >= 1


def test_names_and_files_keep_to_the_contract():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[group]:
            assert NAME.match(e["name"]), e["name"]
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        cfg = common.load_json(os.path.join(common.CHECKOUT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    layers = {m["moves"] for m in SPEC["per_layer"]}
    assert layers <= {m["name"] for m in SPEC["end_to_end"]}
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_union_and_idle_labels():
    ops = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (6.0, 7.0)]
    assert trace_reduce.union(ops) == [[0.0, 2.0], [3.0, 4.0], [6.0, 7.0]]
    host = [(1.9, 3.1, "engine.decode"), (4.0, 5.5, "logits_to_host")]
    labels = trace_reduce.host_labels(host, [(2.0, 3.0), (4.0, 6.0)])
    assert labels == ["engine.decode", "logits_to_host"]
    names = ["%fusion.12 = f32[2,3]{1,0} fusion(f32[2]{0} %p)"] * 3 + [
        "%dec_fn.4 = f32[8]{0} custom-call(f32[8]{0} %q)"]
    trace = {"devices": {"/device:TPU:0": {
        "ops": [(a, b, n) for (a, b), n in zip(ops, names)],
        "modules": [(0.0, 7.0, "jit_dec_fn(-3)")]}}, "host": host}
    red = trace_reduce.reduce(trace, 8.0)
    assert red["busy_s"] == pytest.approx(4.0)
    assert red["modules"] == {"jit_dec_fn": 7.0}
    assert red["breakdown"]["device_ops"] == [["fusion f32[2,3]", 3.5],
                                              ["dec_fn f32[8]", 1.0]]
    assert trace_reduce.kernel_seconds(red, "dec_fn") == 1.0
    assert dict(red["breakdown"]["idle_gaps"]) == pytest.approx(
        {"logits_to_host": 2.0, "engine.decode": 1.0})


def test_lengths_are_one_multiset_for_every_seed():
    """Every block holds the same length pairs, in the same order for every
    seed (the schedule of work is the seed's to keep); the seed draws the
    token ids."""
    from bench.drivers import serve_backlog
    traffic = common.load_json(os.path.join(common.BENCH, "traffic",
                                            "serve.chat.json"))
    k = traffic["block"]

    def pairs(seed):
        p, o = serve_backlog.make_requests(traffic, 1000, seed)
        return p, list(zip(map(len, p), o.tolist()))

    (pa, a), (pb, b) = pairs(1), pairs(2**40 + 1)
    assert a == b
    blocks = [sorted(a[i:i + k]) for i in range(0, len(a) - k + 1, k)]
    assert all(x == blocks[0] for x in blocks)
    assert a[:k] != a[k:2 * k]
    assert not np.array_equal(pa[0], pb[0])
