"""The numbers that decide ``correct``, each against the reference.

Training (the first three steps of the timed call):

* ``loss_gap``: the widest relative gap between a step's loss and the
  reference's;
* ``grad_gap``: over weights, the widest gap between the norm of the
  first gradient as the optimizer took it and the reference's, relative
  to the larger of that weight's reference norm and the median weight's;
* ``update_gap``: the same for the change of the weights over the three
  steps, as stored.  Weights whose reference gradient is under a
  thousandth of the median weight's move by round-off alone and are left
  out.

Serving: ``logit_gap``, the widest gap by which a served token's logit
lies below the reference's best at that position.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np

#: a weight whose reference gradient norm is under this share of the
#: median weight's is not held to the update comparison
STILL = 1e-3


def norm(x) -> float:
    return float(np.linalg.norm(np.asarray(x, np.float64).reshape(-1)))


def leaf_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             keys: Iterable[str]) -> Tuple[float, str]:
    keys = list(keys)
    rn = {k: norm(ref[k]) for k in keys}
    floor = float(np.median(list(rn.values())))
    worst, at = 0.0, ""
    for k in keys:
        gap = abs(norm(prog[k]) - rn[k]) / max(rn[k], floor, 1e-30)
        if gap >= worst:
            worst, at = gap, k
    return worst, at


def train_numbers(prog: Dict, ref: Dict) -> Dict[str, Tuple[float, str]]:
    """``prog``/``ref``: ``losses`` (3), ``grad0``, ``params0`` and
    ``params`` (weight name -> host array)."""
    loss = max(abs(p - r) / abs(r)
               for p, r in zip(prog["losses"], ref["losses"]))
    keys = sorted(ref["grad0"])
    gnorm = {k: norm(ref["grad0"][k]) for k in keys}
    med = float(np.median(list(gnorm.values())))
    moving = [k for k in keys if gnorm[k] >= STILL * med]

    def change(side):
        return {k: np.asarray(side["params"][k], np.float64)
                - np.asarray(side["params0"][k], np.float64)
                for k in moving}

    return {"loss_gap": (float(loss), "steps 0-2"),
            "grad_gap": leaf_gap(prog["grad0"], ref["grad0"], keys),
            "update_gap": leaf_gap(change(prog), change(ref), moving)}


def widest(gaps) -> float:
    return float(max((float(np.max(g)) for g in gaps if len(g)),
                     default=0.0))
