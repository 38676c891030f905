"""A run with its timed path broken underneath must come out not correct.

Each test drives the rest of a run (the harness's look for a chip left
out) at a tiny size on the CPU, with one fault planted where the work is
produced, and checks that `correct` is false:

  serve: a token altered where it is produced; a decode step that leaves
         the cache as it was; half of the batch left out of a step;
  train: a step that returns the state unchanged (zero update); half of
         the batch left out, the mean taken over the rest.

One chip holds these cells whole, so there is no exchange between chips
to leave out.

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import numpy as np
import pytest

from bench.tests import tiny
from bench import run as bench_run


def run_serve(monkeypatch, arch, fault):
    tiny.register(monkeypatch)
    spec = tiny.spec("w", ["serve_tokens_per_s", "itl_p95_ms", "setup_s"])
    files = (spec["workloads"][0], tiny.cfg_of(arch), tiny.SERVE_TRAFFIC,
             {"max_batch": 4, "limits": {"logit_err": 1e-3}})
    return bench_run.run_cell("w", 2**31 + 99, 0.5, False,
                              require_chips=False, spec=spec, files=files,
                              fault=fault)


def alter_tokens(eng):
    """Every 3rd decode step, each live row's best token loses to another."""
    decode = eng.decode
    n = [0]

    def faulty(tokens, cache, pos):
        logits, cache = decode(tokens, cache, pos)
        n[0] += 1
        if n[0] % 3 == 0:
            logits = np.array(logits)
            rows = np.arange(logits.shape[0])
            best = logits.argmax(-1)
            logits[rows, (best + 1) % logits.shape[-1]] = logits.max() + 1.0
        return logits, cache

    eng.decode = faulty


def stale_state(eng):
    """Decode steps compute, but the cache keeps its old contents."""
    decode = eng.decode

    def faulty(tokens, cache, pos):
        tree = dict(cache.tree)
        logits, cache = decode(tokens, cache, pos)
        cache.tree = tree
        return logits, cache

    eng.decode = faulty


def half_batch(eng):
    """Decode steps answer only the first half of the rows."""
    decode = eng.decode

    def faulty(tokens, cache, pos):
        logits, cache = decode(tokens, cache, pos)
        logits = np.array(logits)
        logits[logits.shape[0] // 2:] = 0.0
        return logits, cache

    eng.decode = faulty


@pytest.mark.parametrize("fault", [alter_tokens, stale_state, half_batch],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("arch", [tiny.QWEN, tiny.DANUBE],
                         ids=lambda a: a.name)
def test_serve_fault_is_not_correct(monkeypatch, arch, fault):
    res = run_serve(monkeypatch, arch, fault)
    assert not res["correct"], res["_record"]["check"]


def run_train(monkeypatch, fault):
    from repro.core import wave
    from repro.optim import make_optimizer
    tiny.register(monkeypatch)
    tr = tiny.TRAIN_TRAFFIC
    inner = wave.build_local_wave_step(tiny.QWEN, tr["microbatches"],
                                       make_optimizer("sgd", tr["lr"]))
    spec = tiny.spec("w", ["train_tokens_per_s", "setup_s"])
    files = (spec["workloads"][0], tiny.cfg_of(tiny.QWEN), tr,
             {"limits": {"loss1_gap": 2e-3, "grad_gap": 1e-3,
                         "change_gap_median": 1e-3}})
    return bench_run.run_cell("w", 12345, 0.5, False, require_chips=False,
                              spec=spec, files=files,
                              wave_step=fault(inner))


def unchanged_state(inner):
    import jax

    def step(params, opt_state, x, y):
        deltas, opt_state, loss = inner(params, opt_state, x, y)
        return jax.tree.map(np.zeros_like, deltas), opt_state, loss

    return step


def half_rows(inner):
    def step(params, opt_state, x, y):
        h = x.shape[0] // 2
        x = np.concatenate([x[:h], x[:h]])
        y = np.concatenate([y[:h], y[:h]])
        return inner(params, opt_state, x, y)

    return step


@pytest.mark.parametrize("fault", [unchanged_state, half_rows],
                         ids=lambda f: f.__name__)
def test_train_fault_is_not_correct(monkeypatch, fault):
    res = run_train(monkeypatch, fault)
    assert not res["correct"], res["_record"]["check"]


def test_unchanged_state_reads_one_by_both_leaf_measures():
    """A step that leaves the state unchanged reads 1 by the worst leaf and
    about 1 by the median leaf, with no run: set_limits takes 1 as that
    fault's reading of both."""
    from bench.reference import train_check
    rng = np.random.default_rng(3)
    ref = ([1.0], rng.uniform(0.1, 2.0, 41), rng.uniform(0.1, 2.0, 41))
    zero = ([1.0], np.zeros(41), np.zeros(41))
    gaps = train_check.compare(ref, zero)
    assert gaps["grad_gap"] == 1.0 and gaps["change_gap"] == 1.0
    assert gaps["change_gap_median"] >= 0.99
