"""The comparison that decides `correct` for a training cell.

Three numbers, each the gap between what the timed path read and what the
reference read from the same seed:

  loss_gap    the largest relative gap of a compared round's mean loss;
  grad_gap    over every worker and leaf, the gap between the norms of the
              first moment after the first round (the gradient as the
              optimizer got it);
  change_gap  over every worker and moved leaf, the gap between the norms
              of the parameter change after the last compared round.

A norm gap is |norm(program) - norm(reference)| over the larger of the
reference's norm of that leaf and of the median leaf (of that worker), so
that a leaf whose gradient is all but zero does not decide alone.  A leaf
whose reference gradient norm is under GRAD_FLOOR of the median leaf's
moves by round-off alone under Adam and is left out of change_gap.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
GRAD_FLOOR = 1e-3


def lead_norms(tree) -> list:
    """Per-leaf L2 norms [W] of a tree of [W, ...] leaves (traceable)."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)).reshape(
        x.shape[0], -1), axis=1)) for x in jax.tree.leaves(tree)]


def lead_change_norms(tree, p0) -> list:
    """Per-leaf norms [W] of each worker's change from p0 (traceable)."""
    return lead_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b[None], tree, p0))


def by_leaf(paths: list[str], norms) -> dict[str, np.ndarray]:
    return {p: np.asarray(n, np.float64) for p, n in zip(paths, norms)}


def _median(by_leaf: dict, w: int, leaves) -> float:
    return float(np.median([by_leaf[p][w] for p in leaves]))


def norm_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """(worst gap, "leaf[worker]" where it is) over the kept leaves."""
    worst, where = 0.0, ""
    for w in range(len(next(iter(ref.values())))):
        leaves = [p for p in ref if keep is None or keep(p, w)]
        med = _median(ref, w, leaves)
        for p in leaves:
            gap = abs(prog[p][w] - ref[p][w]) / max(ref[p][w], med)
            if not gap <= worst:          # NaN always takes the lead
                worst, where = gap, f"{p}[{w}]"
    return worst, where


def gaps(prog: dict, ref: dict) -> dict[str, tuple[float, str]]:
    """{number: (value, where)} for one run's readings against the
    reference's (see bench/reference/train.py for their shape)."""
    loss = [abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                ref["losses"])]
    worst = max(range(len(loss)), key=lambda i: (math.isnan(loss[i]),
                                                 loss[i]))
    med = {w: _median(ref["grad"], w, ref["grad"])
           for w in range(len(next(iter(ref["grad"].values()))))}
    moved = lambda p, w: ref["grad"][p][w] >= GRAD_FLOOR * med[w]
    return {"loss_gap": (loss[worst], f"round {worst + 1}"),
            "grad_gap": norm_gap(prog["grad"], ref["grad"]),
            "change_gap": norm_gap(prog["change"], ref["change"], moved)}


def judge(found: dict, limits: dict) -> bool:
    """True when every number is finite and within its limit."""
    return all(found[k][0] <= limits[k] for k in NUMBERS)
