"""The numbers that decide `correct`, each held to its limit.

A cell's limits file (limits/<workload>.json) gives each number's limit
and the readings it was set from; a run is correct when every number is
at or under its limit.
"""
from __future__ import annotations

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# moves under AdamW by round-off alone (a key bias under softmax): its
# gradient and change are not compared
NOUGHT_GRAD = 1e-3


def rel_gap(got, ref) -> float:
    """Largest gap of any element, over the reference's root mean square."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return float("inf")
    rms = float(np.sqrt(np.mean(ref * ref)))
    return float(np.max(np.abs(got - ref)) / rms)


def abs_gap(got, ref) -> float:
    """Largest absolute gap of any element (0 for an exact match)."""
    got = np.asarray(got)
    ref = np.asarray(ref)
    if got.shape != ref.shape:
        return float("inf")
    return float(np.max(np.abs(got.astype(np.float64)
                               - ref.astype(np.float64))))


def _leaf_gap(got, ref, keep) -> float:
    """Worst leaf: the gap between the two norms over the larger of the
    reference leaf's norm and the median leaf's."""
    got, ref = np.asarray(got)[keep], np.asarray(ref)[keep]
    floor = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(got - ref) / floor))


def training_gaps(prog: dict, ref: dict) -> dict:
    """loss_gap: worst step's relative loss gap; grad_gap: worst leaf of the
    first gradient as the optimizer got it; change_gap: worst leaf of the
    parameters' change after three steps."""
    gr = np.asarray(ref["grad_norms"])
    keep = gr >= NOUGHT_GRAD * np.median(gr)
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    if lp.shape != lr.shape or prog["grad_norms"] is None \
            or prog["change_norms"] is None:
        return {"loss_gap": float("inf"), "grad_gap": float("inf"),
                "change_gap": float("inf")}
    return {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_gap": _leaf_gap(prog["grad_norms"], gr, keep),
            "change_gap": _leaf_gap(prog["change_norms"],
                                    ref["change_norms"], keep),
            "leaves_left_out": int((~keep).sum())}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over every number that has a
    limit; a number without a limit, or a limit without a number, is not
    correct."""
    out, ok = {}, True
    for name, lim in limits.items():
        if name.startswith("_"):
            continue
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= lim["limit"]
        ok = ok and bool(good)
        out[name] = {"value": v, "limit": lim["limit"]}
    if not out:
        ok = False
    return ok, out
