"""What decides `correct` in a serve cell.

After the window, a sample of the requests the timed path finished, drawn
from the seed and holding the longest, is run once through the plain
reference (bench/reference/model.py) at full precision: the prompt and the
served tokens as one sequence, no cache. Served token k of a request sits
at position len(prompt) - 1 + k. Two numbers, each the widest over every
served token of the sample:

  logit_err  |the program's logit of the served token (as the Scheduler
             read it when it picked the token) - the reference's logit of
             that token|: the number compared;
  max_gap    how far the reference's logit of the served token lies below
             the reference's best (0 where the picks agree): a reading.
             The picks agree at nearly every position, so it is nonzero
             only at near-ties, for the program and its control alike.

The control is the same reference at fp8 operands (model.CONTROL) put in
the program's place: at the same positions, the token the control puts
first, its logit as the control computed it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import model

CHUNK = 512                 # positions per block of logits


def sample(requests, rng, min_tokens: int, max_requests: int):
    """The longest finished request, then others in a seeded order, until
    `min_tokens` served tokens or `max_requests` requests."""
    done = [r for r in requests if r["tokens"]]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i]["tokens"]))
    rest = [i for i in rng.permutation(len(done)) if i != longest]
    picked, n = [done[longest]], len(done[longest]["tokens"])
    for i in rest:
        if n >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(done[i])
        n += len(done[i]["tokens"])
    return picked


def build(cfg: dict, length: int, control: bool):
    """jitted (params, seq [length], first, count, served [length]) ->
    per-position (gap, err) [length] each (-1 where no served token is
    read). `served` holds the program's logit of each served token."""
    def gaps(params, seq, first, count, served):
        h = model.hidden(cfg, params, seq)
        hc = None
        if control:
            hc = model.hidden(cfg, params, seq, model.CONTROL)
        gap, err = [], []
        for c0 in range(0, length, CHUNK):
            pos = c0 + jnp.arange(min(CHUNK, length - c0))
            lg = model.logits(cfg, params, h[c0:c0 + CHUNK])
            best = jnp.max(lg, axis=-1)
            if control:
                lc = model.logits(cfg, params, hc[c0:c0 + CHUNK],
                                  model.CONTROL)
                tok = jnp.argmax(lc, axis=-1)
                val = jnp.max(lc, axis=-1)
            else:
                tok = seq[jnp.minimum(pos + 1, length - 1)]
                val = served[c0:c0 + CHUNK]
            got = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
            live = (pos >= first) & (pos < first + count)
            gap.append(jnp.where(live, best - got, -1.0))
            err.append(jnp.where(live, jnp.abs(val - got), -1.0))
        return jnp.concatenate(gap), jnp.concatenate(err)

    return jax.jit(gaps)


def compare(cfg: dict, params, picked, length: int,
            control: bool = False) -> dict:
    """The check's numbers over `picked` ({prompt, tokens, logits})."""
    fn = build(cfg, length, control)
    gap = err = float("-inf")
    for r in picked:
        prompt = np.asarray(r["prompt"], np.int32)
        toks = np.asarray(r["tokens"], np.int32)
        seq = np.zeros(length, np.int32)
        full = np.concatenate([prompt, toks])[:length]
        seq[:full.shape[0]] = full
        first = len(prompt) - 1
        served = np.zeros(length, np.float32)
        vals = np.asarray(r["logits"], np.float32)[:length - first]
        served[first:first + vals.shape[0]] = vals
        g, e = fn(params, seq, first, len(toks), served)
        gap, err = max(gap, float(np.max(g))), max(err, float(np.max(e)))
    if not picked:
        gap = err = float("inf")
    return {"logit_err": err, "max_gap": gap,
            "sampled_requests": len(picked),
            "sampled_tokens": sum(len(r["tokens"]) for r in picked)}
