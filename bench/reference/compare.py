"""The numbers that decide ``correct`` for a training cell.

A run drives the program's compiled cycle from the seed through its
first three cycles and keeps, per replica: each cycle's mean loss, the
optimizer's first-moment state after the first cycle (the gradients as
the optimizer got them: RMSProp's ``g``, Adam's ``m``), and the
parameters before the first cycle and after the third. The
configuration's reference (``bench/reference/<config>.py``, else the
shared ``cycle.py``) does the same from the same seed. Four numbers
compare them:

* ``first_loss_gap``: |program - reference| / |reference| of the first
  cycle's loss. Its learner samples only the prepopulated replay with
  the initial parameters, so nothing but rounding separates the two;
* ``loss_gap``: the largest such gap of the three cycle losses;
* ``grad_gap``: over the leaves, the largest gap between the program's
  and the reference's norm of the first moment, over the reference's
  norm of that leaf or of the median leaf, whichever is larger;
* ``change_gap``: the same for the norm of the parameters' change over
  the three cycles, leaving out leaves whose reference first moment has
  an RMS under a thousandth of the median leaf's (such a leaf moves by
  round-off alone).
"""

from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

NUMBERS = ("first_loss_gap", "loss_gap", "grad_gap", "change_gap")
STILL_LEAF = 1e-3
# a reading that is not a finite number (a NaN loss) prints as this, so
# the result line stays strict JSON
NOT_FINITE = 1e300


def _norms(tree: Dict[str, np.ndarray]) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in tree.items()}


def _rms(tree: Dict[str, np.ndarray]) -> Dict[str, float]:
    return {k: float(np.sqrt(np.mean(np.square(np.asarray(v, np.float64)))))
            for k, v in tree.items()}


def _worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
                keep: Iterable[str]) -> float:
    keep = list(keep)
    med = float(np.median([ref[k] for k in keep]))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keep]
    return float(np.max(gaps))      # NaN if any leaf is NaN


def readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog``/``ref``: {"loss": (3,), "moment": {leaf: array},
    "params0": {leaf: array}, "params3": {leaf: array}} of one replica."""
    lp = np.asarray(prog["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    loss_gaps = np.abs(lp - lr) / np.maximum(np.abs(lr), 1e-30)
    loss_gap = float(np.max(loss_gaps))
    leaves = sorted(ref["moment"])
    grad_gap = _worst_leaf(_norms(prog["moment"]), _norms(ref["moment"]),
                           leaves)
    rms = _rms(ref["moment"])
    med_rms = float(np.median(list(rms.values())))
    moving = [k for k in leaves if rms[k] >= STILL_LEAF * med_rms]
    delta = lambda t: {k: np.asarray(t["params3"][k], np.float64)  # noqa: E731
                       - np.asarray(t["params0"][k], np.float64)
                       for k in leaves}
    change_gap = _worst_leaf(_norms(delta(prog)), _norms(delta(ref)), moving)
    out = {"first_loss_gap": float(loss_gaps[0]), "loss_gap": loss_gap, "grad_gap": grad_gap,
           "change_gap": change_gap}
    return {k: (v if np.isfinite(v) else NOT_FINITE) for k, v in out.items()}


def still_leaves(ref: Dict) -> list:
    """The leaves ``change_gap`` leaves out for this reference run."""
    rms = _rms(ref["moment"])
    med = float(np.median(list(rms.values())))
    return sorted(k for k, v in rms.items() if v < STILL_LEAF * med)


def worst(per_replica: Iterable[Dict[str, float]]) -> Dict[str, float]:
    per_replica = list(per_replica)
    return {k: max(r[k] for r in per_replica) for k in NUMBERS}
