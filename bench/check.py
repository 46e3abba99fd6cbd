"""How ``correct`` is decided: the program's answers against the replay.

Every number below is computed from one program result (the arrays a
``repro.run`` returned) and the plain reference replaying that result's
selections (``bench/reference.py``). Each is held to a limit kept in
``bench/limits/<workload>.json``; the readings each limit was set from
are in ``PERF.md``. Only the numbers that file names are compared (a
number that neither the control nor a fault moves has no limit and is
not compared); a named number that a run cannot compute makes it not
correct.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))


def numbers(result: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
            budget: float) -> Dict[str, float]:
    """The compared numbers (all of them "lower is better")."""
    sel = np.asarray(result["selections"])
    if sel.shape != ref["pred"].shape:
        return {"shape_mismatch": 1.0}
    rows = sel.shape[0] * sel.shape[1]
    picks = max(1, int((sel >= 0).sum()))
    out = {
        # a row is one (seed, round): the program's whole selection
        "selection_rows_differ": float(
            np.any(sel != ref["pred"], axis=-1).sum() / rows),
        # participants and the utility they give, per (seed, round)
        "outcome_rows_differ": float(
            ((np.asarray(result["participants"]) != ref["participants"])
             | (np.abs(np.asarray(result["utilities"], np.float64)
                       - ref["utilities"]) > 1e-6)).sum() / rows),
        "explored_rows_differ": float(
            (np.asarray(result["explored"]) != ref["explored"]).sum() / rows),
        # the reference's costs of the program's picks, per ES and round
        "budget_overrun": float(max(0.0, ref["spend"].max() / budget - 1.0)),
        "ineligible_picks": float(ref["ineligible"].sum() / picks),
    }
    if "loss" in ref:
        loss_p = np.asarray(result["loss"], np.float64)
        acc_p = np.asarray(result["accuracy"], np.float64)
        if loss_p.shape != ref["loss"].shape or not np.all(np.isfinite(loss_p)):
            out["loss_gap"] = out["accuracy_gap"] = float("inf")
        else:
            out["loss_gap"] = float(np.max(np.abs(loss_p - ref["loss"])
                                           / np.abs(ref["loss"])))
            out["accuracy_gap"] = float(np.max(np.abs(acc_p - ref["accuracy"])))
    return out


def limits(workload: str, root: Optional[str] = None) -> Dict[str, float]:
    path = os.path.join(root or BENCH, "limits", f"{workload}.json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def judge(nums: Dict[str, float], lims: Dict[str, float]):
    """(correct, [[name, number, limit], ...]) over the limited numbers."""
    rows, ok = [], bool(lims)
    for name in sorted(lims):
        num = nums.get(name)
        rows.append([name, num, lims[name]])
        if num is None or not num <= lims[name]:
            ok = False
    return ok, rows


def worst(readings):
    """Elementwise maximum over several replays' numbers."""
    out: Dict[str, float] = {}
    for nums in readings:
        for k, v in nums.items():
            out[k] = max(out.get(k, v), v)
    return out
