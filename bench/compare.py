"""The numbers that decide ``correct``, from the program's readings and
the reference's.

* ``loss_gap``: the largest relative gap of a step's loss over the
  steps up to the first update that applies a gradient (``steady``
  of them).  A later step's loss follows an Adam step whose sign-like
  update flips with rounding, so it swings from seed to seed.
* ``grad_gap``: over the leaves, the largest gap between the norm of
  the first gradient the optimizer applied in the program (read from
  its Adam state) and in the reference, relative to the larger of that
  leaf's reference norm and the median leaf's.
* ``grad_diff``: over the leaves, the largest norm of the difference
  of those two gradients, relative to the same.  A gap of norms hides
  rounding that averages out over a leaf; the difference does not, so
  this is the number that a lower precision fails.
* ``update_gap``: the same for the norm of each leaf's change over the
  steps followed.  A leaf whose reference gradient is under a
  thousandth of the median leaf's is left out: Adam moves it by
  rounding alone.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Sequence, Tuple

import numpy as np

NEGLIGIBLE = 1e-3


def _worst_leaf(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray):
    floor = max(float(np.median(ref)), 1e-30)
    rel = np.abs(prog - ref) / np.maximum(ref, floor)
    rel = np.where(keep, rel, 0.0)
    i = int(np.argmax(rel))
    return float(rel[i]), i, float(prog[i]), float(ref[i])


def numbers(prog: Dict[str, Sequence[float]], ref: Dict[str, Sequence[float]],
            names: Sequence[str], steady: int) -> Dict[str, Dict[str, object]]:
    """{number: {"value", "leaf", ...}} from both sides' per-step
    ``losses``, the leaves of the first applied gradient (``first_grad``)
    and the per-leaf norms of the weights' change (``delta``)."""
    lp = np.asarray(prog["losses"][:steady], np.float64)
    lr = np.asarray(ref["losses"][:steady], np.float64)
    out: Dict[str, Dict[str, object]] = {
        "loss_gap": {"value": float(np.max(np.abs(lp - lr) / np.abs(lr)))}}
    norm = lambda x: float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))
    gr = np.asarray([norm(x) for x in ref["first_grad"]])
    gp = np.asarray([norm(x) for x in prog["first_grad"]])
    v, i, a, b = _worst_leaf(gp, gr, np.ones_like(gr, bool))
    out["grad_gap"] = {"value": v, "leaf": names[i], "program": a,
                       "reference": b}
    diff = np.asarray([norm(np.asarray(x, np.float64) - y) for x, y in
                       zip(prog["first_grad"], ref["first_grad"])])
    rel = diff / np.maximum(gr, max(float(np.median(gr)), 1e-30))
    i = int(np.argmax(rel))
    out["grad_diff"] = {"value": float(rel[i]), "leaf": names[i]}
    keep = gr >= NEGLIGIBLE * np.median(gr)
    v, i, a, b = _worst_leaf(np.asarray(prog["delta"], np.float64),
                             np.asarray(ref["delta"], np.float64), keep)
    out["update_gap"] = {"value": v, "leaf": names[i], "program": a,
                         "reference": b,
                         "left_out": [n for n, k in zip(names, keep)
                                      if not k]}
    return out


def verdict(nums: Dict[str, Dict[str, object]], limits: Dict[str, Any]
            ) -> Tuple[bool, Dict[str, Dict[str, object]]]:
    """(every number finite and within its limit, {number: {"value",
    "limit"}}); a number without a limit fails."""
    ok, checks = True, {}
    for k, v in nums.items():
        lim = limits.get(k)
        ok &= lim is not None and math.isfinite(v["value"]) \
            and v["value"] <= lim
        checks[k] = {"value": v["value"], "limit": lim}
    return bool(ok), checks
