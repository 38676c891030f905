"""The plain references against the program's path, at a tiny size on the
CPU (f32, where the program computes at full precision): the served tokens
are the reference's best, the training steps agree to rounding, and the
control, the same reference at fp8 operands, reads far above both.

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests import tiny
from bench import run as bench_run
from bench.reference import serve_check


def serve_record(monkeypatch, arch, seed):
    tiny.register(monkeypatch)
    spec = tiny.spec("w", ["serve_tokens_per_s", "itl_p95_ms", "setup_s"])
    files = (spec["workloads"][0], tiny.cfg_of(arch), tiny.SERVE_TRAFFIC,
             {"max_batch": 4, "limits": {"logit_err": 1e-3}})
    return bench_run.run_cell("w", seed, 0.5, False, require_chips=False,
                              spec=spec, files=files)


@pytest.mark.parametrize("arch", [tiny.QWEN, tiny.DANUBE],
                         ids=lambda a: a.name)
def test_served_tokens_are_the_references_best(monkeypatch, arch):
    res = serve_record(monkeypatch, arch, 2**33 + 11)
    rec = res["_record"]
    assert rec["check"]["sampled_tokens"] >= 40
    assert rec["check"]["max_gap"] < 1e-4
    assert rec["check"]["logit_err"] < 1e-3
    assert res["correct"]


@pytest.mark.parametrize("arch", [tiny.QWEN, tiny.DANUBE],
                         ids=lambda a: a.name)
def test_control_fails_the_serve_comparison(monkeypatch, arch):
    """The control's logits of its first tokens lie far further from the
    full-precision reference's than the program's do."""
    res = serve_record(monkeypatch, arch, 5)
    rec = res["_record"]
    from repro.models import lm
    from bench import common
    cfg = tiny.cfg_of(arch)
    params = common.make_params(lm.param_shapes(arch), arch.num_layers, 5)
    finished = rec["finished"]
    picked = serve_check.sample(finished, np.random.default_rng(0), 10**6, 64)
    sv = tiny.SERVE_TRAFFIC
    ctl = serve_check.compare(cfg, params, picked,
                              sv["prompt_len"] + sv["gen"], control=True)
    prog = serve_check.compare(cfg, params, picked,
                               sv["prompt_len"] + sv["gen"])
    assert prog["logit_err"] < 1e-3 and prog["max_gap"] < 1e-4
    assert ctl["logit_err"] > 10 * max(prog["logit_err"], 1e-5)


def train_record(monkeypatch, seed, **kw):
    tiny.register(monkeypatch)
    spec = tiny.spec("w", ["train_tokens_per_s", "setup_s"])
    files = (spec["workloads"][0], tiny.cfg_of(tiny.QWEN), tiny.TRAIN_TRAFFIC,
             {"limits": {"loss1_gap": 2e-3, "grad_gap": 1e-3,
                         "change_gap_median": 1e-3}})
    return bench_run.run_cell("w", seed, 0.5, False, require_chips=False,
                              spec=spec, files=files, **kw)


def test_train_steps_agree_and_control_fails(monkeypatch):
    res = train_record(monkeypatch, 2**32 + 3,
                       readings=("program", "control"))
    rec = res["_record"]
    prog, ctl = rec["check"], rec["readings"]["control"]
    assert res["correct"], rec["check"]
    # the program rounds its loss's logits to bf16 (chunked_cross_entropy),
    # so the loss gap is not rounding-free even on the CPU
    assert prog["grad_gap"] < 1e-3 and prog["change_gap"] < 1e-3
    assert ctl["grad_gap"] > 2 * prog["grad_gap"]
    assert ctl["change_gap"] > 2 * prog["change_gap"]
    assert jnp.isfinite(jnp.asarray(rec["check_losses"]["reference"])).all()


def test_replay_reads_each_leafs_base():
    """A pull is consistent per leaf only: a step is replayed from the
    weights whose every leaf holds the number of earlier updates that the
    run recorded for that leaf."""
    import jax
    from bench import common
    from bench.reference import train_check
    from repro.models import lm
    cfg = tiny.cfg_of(tiny.QWEN)
    params = common.make_params(lm.param_shapes(tiny.QWEN),
                                tiny.QWEN.num_layers, 7)
    n = len(jax.tree.leaves(params))
    rng = np.random.default_rng(7)
    x = rng.integers(0, tiny.QWEN.vocab_size, (2, 16), dtype=np.int32)
    y = rng.integers(0, tiny.QWEN.vocab_size, (2, 16), dtype=np.int32)

    def second_loss(base):
        steps = [{"x": x, "y": y, "base": 0}, {"x": x, "y": y, "base": base}]
        return train_check.replay(cfg, params, steps, 0.3)[0][1]

    assert second_loss([1] * n) == second_loss(1)
    assert second_loss([0] * n) == second_loss(0)
    mixed = second_loss([1] * (n // 2) + [0] * (n - n // 2))
    assert mixed not in (second_loss(0), second_loss(1))
