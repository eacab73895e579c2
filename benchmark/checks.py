"""The comparison that decides ``correct``: each number beside its limit.

Limits come from the job or traffic file's ``limits`` group (how each was set
is in PERF.md). A check is ``{"value": v, "limit": l}`` and holds when
``v <= l``.
"""

from __future__ import annotations

import numpy as np


def _flat(norms: dict) -> tuple[list[str], np.ndarray]:
    names, vals = [], []
    for k in sorted(norms):
        v = np.atleast_1d(np.asarray(norms[k], np.float64))
        names += [f"{k}[{i}]" if len(v) > 1 else k for i in range(len(v))]
        vals += list(v)
    return names, np.asarray(vals)


def worst_leaf_gap(program: dict, ref: dict, keep=None) -> tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the reference's,
    against the reference's norm of that leaf or of the median leaf, whichever
    is larger (some gradients are all but zero)."""
    names, p = _flat(program)
    _, r = _flat(ref)
    gap = np.abs(p - r) / np.maximum(r, np.median(r))
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), names[i]


def train(program: dict, ref: dict, limits: dict) -> dict:
    """Three losses, the first gradient and the change after three steps."""
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], ref["losses"]), 1):
        out[f"loss{i}_gap"] = {"value": abs(a - b) / abs(b), "limit": limits["loss_gap"],
                               "program": a, "reference": b}
    g, at = worst_leaf_gap(program["grad_norms"], ref["grad_norms"])
    out["grad_norm_gap"] = {"value": g, "limit": limits["grad_norm_gap"], "leaf": at}
    # a leaf whose gradient is nought to rounding in the reference (under a
    # thousandth of the median leaf's) moves under Adam by round-off alone
    _, rg = _flat(ref["grad_norms"])
    moved = rg >= 1e-3 * np.median(rg)
    d, at = worst_leaf_gap(program["delta_norms"], ref["delta_norms"], keep=moved)
    out["delta_norm_gap"] = {"value": d, "limit": limits["delta_norm_gap"], "leaf": at,
                             "leaves_left_out": int((~moved).sum())}
    return out


def holds(checks: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
