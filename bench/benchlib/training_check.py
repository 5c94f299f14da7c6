"""The comparison that decides a training cell's ``correct``.

The program and the plain reference each run the cell's first steps from
the same seed (a step is one call of the window's entry: a PPO iteration,
an A3C round).  Each side is summarised as :class:`FirstSteps`:

* ``losses``: the loss each step reports;
* ``moment1``: Adam's first moment after step 1, the gradients as the
  optimizer got them (clipped), exponentially averaged over the step's
  updates; after one update it is the gradient times ``1 - beta1``;
* ``delta``: each parameter's change over the first three steps.

Three numbers are compared, each against a limit of its own:

* ``loss_gap``: the largest ``|loss - loss_ref|`` over the steps, each
  over the larger of ``|loss_ref|`` and the median step's (a loss may
  cross zero);
* ``grad_gap`` and ``delta_gap``: by the worst leaf, the gap between the
  program's norm of the leaf and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf.

Leaves whose reference first moment is under a thousandth of the median
leaf's move under Adam by round-off alone: they are left out of
``delta_gap`` (none of the Table-6 policies has one; the rule is kept so
that a configuration that does is judged soundly).
"""
from __future__ import annotations

import math
from typing import Dict, List, NamedTuple

import numpy as np

import jax

NUMBERS = ("loss_gap", "grad_gap", "delta_gap")
NEGLIGIBLE = 1e-3


class FirstSteps(NamedTuple):
    losses: List[float]
    moment1: Dict[str, np.ndarray]
    delta: Dict[str, np.ndarray]


def leaves(tree) -> Dict[str, np.ndarray]:
    """Host copies of a pytree's leaves keyed by path."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    host = jax.device_get([x for _, x in flat])
    return {jax.tree_util.keystr(p): np.asarray(x, np.float64)
            for (p, _), x in zip(flat, host)}


def first_steps(losses, params0, moment1, params3) -> FirstSteps:
    p0, p3 = leaves(params0), leaves(params3)
    return FirstSteps([float(x) for x in losses], leaves(moment1),
                      {k: p3[k] - p0[k] for k in p0})


def _norms(d):
    return {k: float(np.linalg.norm(v)) for k, v in d.items()}


def worst_leaf_gap(prog, ref, keep=None) -> float:
    if set(prog) != set(ref):
        raise ValueError(f"leaf sets differ: {sorted(set(prog) ^ set(ref))}")
    keys = [k for k in ref if keep is None or k in keep]
    np_, nr = _norms(prog), _norms(ref)
    med = float(np.median([nr[k] for k in keys]))
    return max(abs(np_[k] - nr[k]) / max(nr[k], med) for k in keys)


def numbers(prog: FirstSteps, ref: FirstSteps) -> Dict[str, float]:
    if len(prog.losses) != len(ref.losses):
        raise ValueError("programs ran different numbers of steps")
    scale = float(np.median(np.abs(ref.losses)))
    loss_gap = max(abs(p - r) / max(abs(r), scale) if math.isfinite(p)
                   else math.inf for p, r in zip(prog.losses, ref.losses))
    nr = _norms(ref.moment1)
    med = float(np.median(list(nr.values())))
    keep = {k for k, v in nr.items() if v >= NEGLIGIBLE * med}
    return {"loss_gap": loss_gap,
            "grad_gap": worst_leaf_gap(prog.moment1, ref.moment1),
            "delta_gap": worst_leaf_gap(prog.delta, ref.delta, keep)}

