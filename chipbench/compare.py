"""The comparison that decides ``correct`` for a training cell.

Three numbers, each a gap between what the program's first steps gave and
what the reference gave from the same weights and rows:

- ``loss_gap``: the largest relative gap of a step's loss;
- ``grad_gap``: over the leaves, the largest gap between the norms of the
  first clipped gradient, over the reference's norm of that leaf or of the
  median leaf, whichever is larger;
- ``update_gap``: the same for the norms of each leaf's change after the
  last step. Leaves whose reference gradient is under a thousandth of the
  median leaf's are left out: their gradient is nought but for rounding (a
  key bias under softmax), and Adam turns rounding into full-size steps.

Each number has a limit of its own, in ``limits/<workload>.json``; a run is
correct when every number is at or under its limit.
"""

from __future__ import annotations

import numpy as np

SILENT_LEAF = 1e-3


def _worst_gap(prog, ref, keep):
    p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    scale = np.maximum(r, np.median(r[keep]))
    gaps = np.where(keep, np.abs(p - r) / scale, 0.0)
    return float(gaps.max()), int(gaps.argmax())


def numbers(prog: dict, ref: dict) -> dict:
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))
    g_ref = np.asarray(ref["grad"])
    every = np.ones(len(g_ref), bool)
    moving = g_ref >= SILENT_LEAF * np.median(g_ref)
    grad, g_leaf = _worst_gap(prog["grad"], ref["grad"], every)
    update, u_leaf = _worst_gap(prog["delta"], ref["delta"], moving)
    return {"loss_gap": loss, "grad_gap": grad, "update_gap": update,
            "worst_leaf": {"grad_gap": g_leaf, "update_gap": u_leaf},
            "left_out": [i for i in range(len(g_ref)) if not moving[i]]}


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for every number compared."""
    checks = {}
    for name in ("loss_gap", "grad_gap", "update_gap"):
        lim = limits.get(name, {}).get("limit")
        checks[name] = {"value": nums[name], "limit": lim}
    ok = all(c["limit"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
