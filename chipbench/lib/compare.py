"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the plain reference computes.

* ``rel_gap`` of each step's loss;
* ``leaf_norm_gap``: for each leaf, the gap between the program's norm and
  the reference's (not the norm of their difference), over the larger of the
  reference leaf's norm and the median leaf's norm, worst leaf taken. Leaves
  whose reference first gradient is under a thousandth of the median leaf's
  move under Adam by round-off alone and are left out (``counted_leaves``).
"""
from __future__ import annotations

import numpy as np


def rel_gap(a: float, ref: float) -> float:
    if not np.isfinite(a):
        return float("inf")
    return abs(a - ref) / max(abs(ref), 1e-30)


def leaf_norms(tree: dict, prefix: str = "") -> dict[str, float]:
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(leaf_norms(v, name + "."))
        else:
            out[name] = float(np.linalg.norm(np.asarray(v, np.float64)))
    return out


def counted_leaves(ref_grad: dict) -> list[str]:
    norms = leaf_norms(ref_grad)
    med = float(np.median(list(norms.values())))
    return sorted(k for k, v in norms.items() if v >= 1e-3 * med)


def leaf_norm_gap(prog: dict, ref: dict, leaves: list[str]
                  ) -> tuple[float, str]:
    """(worst gap, its leaf) over ``leaves``."""
    pn, rn = leaf_norms(prog), leaf_norms(ref)
    med = float(np.median([rn[k] for k in leaves]))
    worst, where = 0.0, ""
    for k in leaves:
        gap = abs(pn[k] - rn[k]) / max(rn[k], med, 1e-30)
        if not np.isfinite(pn[k]):
            gap = float("inf")
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def tree_sub(a: dict, b: dict) -> dict:
    return {k: tree_sub(v, b[k]) if isinstance(v, dict)
            else np.asarray(v, np.float64) - np.asarray(b[k], np.float64)
            for k, v in a.items()}
