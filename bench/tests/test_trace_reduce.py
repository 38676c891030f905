"""The trace reduction on two traces recorded on a TPU v5e (data/):
three waves of each of two virtual workers of the threaded WSP runtime at
a tiny size (`jit_wave_step`, wsp_tiny.xplane.pb, written by
bench/tools/probe_trace.py); and one prefill group and three decode steps
of qwen3-0.6b at its full widths, 4 slots on the paged pool with both
Pallas kernels (serve_probe.xplane.pb.gz, recorded by an earlier form of
that script, whose tiny_serve() now writes the same steps at a tiny
width).

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import os

import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def wsp():
    return trace_reduce.load(os.path.join(DATA, "wsp_tiny.xplane.pb"))


def test_device_events_are_found(wsp):
    assert list(wsp["devices"]) == ["/device:TPU:0"]
    dev = wsp["devices"]["/device:TPU:0"]
    assert len(dev["ops"]) > 1000 and dev["modules"]
    assert all(a <= b for a, b, _ in dev["ops"])


def test_programs_busy_time_and_breakdown(wsp):
    red = trace_reduce.reduce(wsp, 1.0)
    step = red["modules"]["jit_wave_step"]
    assert step > 0
    # busy time is the union of the ops: no longer than the programs ran,
    # no shorter than the longest program
    assert step <= red["busy_s"] <= sum(red["modules"].values()) * 1.001
    ops = red["breakdown"]["device_ops"]
    assert len(ops) == 10
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    # no harness annotation was open: the idle time is the host's own
    assert [n for n, _ in red["breakdown"]["idle_gaps"]] == ["host (other)"]


def test_kernels_are_found_by_their_steps_names(tmp_path):
    """Each Pallas kernel is a custom call named after the jitted step that
    calls it: `pre_fn` (flash_attention_fwd) and `dec_fn`
    (flash_decode_paged)."""
    import gzip
    import shutil
    path = tmp_path / "serve.xplane.pb"
    with gzip.open(os.path.join(DATA, "serve_probe.xplane.pb.gz")) as f, \
            open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    red = trace_reduce.reduce(trace_reduce.load(str(path)), 1.0)
    for kernel, step in (("pre_fn", "jit_pre_fn"), ("dec_fn", "jit_dec_fn")):
        spent = trace_reduce.kernel_seconds(red, kernel)
        assert 0 < spent < red["modules"][step]
