"""The numbers that decide ``correct``, each against its limit.

Per followed app the program is compared with the reference
(``bench/lib/reference.py``) at both ends of what it ran
(``probe.FOLLOWED_APPLIES`` applies at each):

- the first three applies:
  - ``loss_gap``: the largest relative gap between the program's and
    the reference's mean local loss of an apply;
  - ``update_gap``: the first apply's update (the weights after it minus
    the initial weights), by the worst leaf;
  - ``change_gap``: the change of the weights over the three applies, by
    the worst leaf;
  - ``broadcast_gap`` (compressed broadcasts only): the change over the
    three applies of the state workers download, by the worst leaf;
- the last three applies that ended inside the measured window, where
  staleness and broadcast chains are those of the timed load:
  ``window_loss_gap``, ``window_change_gap`` and
  ``window_broadcast_gap``, the same over those three.

The two ends are kept apart because they read differently: the
reference replays every apply from the initial weights, and by the
window a lattice step that rounded the other way in some earlier commit
or broadcast (a difference of rounding before the stochastic rounding)
has moved both trajectories apart a little.

"By the worst leaf" is the largest over leaves of ``|‖a_leaf‖ -
‖r_leaf‖|`` over the larger of ``‖r_leaf‖`` and the median leaf's
``‖r‖``.  Leaves whose first reference update is under a thousandth of
the median leaf's move by round-off alone and are left out of all the
weight numbers.  A cell's number is the largest over its followed apps.
"""
from __future__ import annotations

import math

import jax
import numpy as np

NEGLIGIBLE = 1e-3  # of the median leaf's first update: moves by round-off alone


def _leaves(tree) -> dict[str, np.ndarray]:
    """The leaves of a weights pytree by their path, in float64."""
    return {jax.tree_util.keystr(path): np.asarray(v, np.float64)
            for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _norms(leaves: dict) -> dict[str, float]:
    return {k: float(np.linalg.norm(v.ravel())) for k, v in leaves.items()}


def _diff(a, b) -> dict:
    la, lb = _leaves(a), _leaves(b)
    return {k: la[k] - lb[k] for k in lb}


def worst_leaf_gap(got: dict, ref: dict, keep: list[str]) -> float:
    ng, nr = _norms(got), _norms(ref)
    med = float(np.median(list(nr.values())))
    gaps = [abs(ng[k] - nr[k]) / max(nr[k], med, 1e-30) for k in keep]
    return max(gaps) if gaps else float("nan")


def app_numbers(params0: dict, program, reference, broadcast: bool) -> dict[str, float]:
    """``program`` and ``reference``: per apply (params, loss, held)
    lists of the same length, at least three applies each."""
    p_params, p_loss, p_held = program
    r_params, r_loss, r_held = reference
    n = len(r_params)
    first = _diff(r_params[0], params0)
    nf = _norms(first)
    med = float(np.median(list(nf.values())))
    keep = [k for k in sorted(nf) if nf[k] >= NEGLIGIBLE * med]

    def at(seq, i):
        return params0 if i < 0 else seq[i]

    def gap(seq_p, seq_r, i, j):
        """The change from after apply ``i`` to after apply ``j``."""
        return worst_leaf_gap(_diff(at(seq_p, j), at(seq_p, i)),
                              _diff(at(seq_r, j), at(seq_r, i)), keep)

    last = n - 1
    out = {
        "loss_gap": max(_rel(p_loss[i], r_loss[i]) for i in range(3)),
        "update_gap": gap(p_params, r_params, -1, 0),
        "change_gap": gap(p_params, r_params, -1, 2),
        "window_loss_gap": max(_rel(p_loss[i], r_loss[i]) for i in range(last - 2, n)),
        "window_change_gap": gap(p_params, r_params, last - 3, last),
    }
    if broadcast:
        out["broadcast_gap"] = gap(p_held, r_held, -1, 2)
        out["window_broadcast_gap"] = gap(p_held, r_held, last - 3, last)
    return out


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def cell_numbers(per_app: list[dict[str, float]]) -> dict[str, float]:
    """The worst reading of each number over the followed apps."""
    names = sorted({k for d in per_app for k in d})
    return {k: max(d[k] for d in per_app) for k in names}


def judge(numbers: dict[str, float], limits: dict[str, float]) -> bool:
    """Correct when every limited number is finite and within its limit."""
    if set(limits) - set(numbers):
        return False
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k] for k in limits)
