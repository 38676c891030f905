"""What decides `correct` in a training cell.

The reference replays the first `steps` parameter-server updates of the
run in the order they were applied. Each update is one virtual worker's
wave: SGD on the mean loss of its rows at the weights it pulled, whose
every leaf holds the first `base` updates (the run records `base` leaf by
leaf: a pull is consistent per leaf, not across leaves). The reference
computes each wave's loss and gradient at full precision from the seed's
weights and the recorded rows (bench/reference/model.py), never from what
the program computed.

Numbers, each a gap against the reference:
  loss1_gap   |program loss - reference loss| of the first step;
  grad_gap    the first update's gradient as the optimizer got it
              (-delta / lr for SGD), by its worst leaf: the gap between
              the program's and the reference's norm of a leaf, over the
              reference's norm of that leaf or of the median leaf,
              whichever is larger;
  change_gap_median
              the weights' change after the steps, as the parameter
              server holds it for the next pull, by its median leaf alike;
  loss_gap, change_gap
              the largest loss gap over the steps and the change by its
              worst leaf: readings only. At the cell's learning rate the
              third step amplifies rounding, so on a few seeds these read
              several times what the others do; the first step's loss and
              the median leaf are steady from seed to seed.
Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of the leaf measures (under SGD they barely move).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import model


def norms(leaves) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(l, np.float64)))
                     for l in leaves])


def leaf_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray,
             reduce=np.max) -> float:
    scale = np.maximum(ref, np.median(ref))
    return float(reduce((np.abs(prog - ref) / scale)[keep]))


def replay(cfg: dict, w0, steps, lr: float, dtype=jnp.float32,
           rows: slice = slice(None)):
    """Losses, and the norms of the first gradient's leaves and of the
    summed update's leaves, of the recorded `steps` ({x, y, base}),
    computed by the reference. Only the updates themselves are kept whole
    (on the host), and only while the replay runs."""
    vg = jax.jit(jax.value_and_grad(
        lambda p, x, y: model.loss(cfg, p, x, y, dtype)))
    leaves0, treedef = jax.tree.flatten(w0)
    deltas, losses, grad1 = [], [], None
    for st in steps:
        w = w0
        base = np.broadcast_to(np.asarray(st["base"]), (len(leaves0),))
        if base.any():
            w = jax.tree.unflatten(treedef, [
                a + jnp.asarray(sum(d[i] for d in deltas[:n]))
                for i, (a, n) in enumerate(zip(leaves0, base))])
        loss, g = vg(w, jnp.asarray(st["x"][rows]), jnp.asarray(st["y"][rows]))
        g = [np.asarray(x) for x in jax.tree.leaves(g)]
        if grad1 is None:
            grad1 = norms(g)
        deltas.append([-lr * x for x in g])
        losses.append(float(loss))
        del w, g
    change = norms(sum(d[i] for d in deltas) for i in range(len(leaves0)))
    return losses, grad1, change


def compare(ref, other) -> dict:
    """Gaps of `other` (losses, grad1 leaf norms, change leaf norms)
    against the reference's; both as replay() returns them."""
    r_loss, rg, rc = ref
    o_loss, og, oc = other
    keep = rg >= 1e-3 * np.median(rg)
    return {"loss1_gap": abs(o_loss[0] - r_loss[0]),
            "grad_gap": leaf_gap(og, rg, keep),
            "change_gap_median": leaf_gap(oc, rc, keep, np.median),
            "loss_gap": max(abs(a - b) for a, b in zip(o_loss, r_loss)),
            "change_gap": leaf_gap(oc, rc, keep),
            "leaves_left_out": int((~keep).sum())}
