"""What both drivers share: the comparison of norms and the device's peak
memory. (The adapter between the benchmark's weight layout and the
program's parameter tree is the family's.)
"""

from __future__ import annotations

import statistics

import numpy as np

from benchmark import reference


def flat_norms(norms: dict) -> dict:
    """{leaf or leaf[layer]: norm} from `reference.leaf_norms` output."""
    out = {}
    for k, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[k] = float(v)
        else:
            out.update({f"{k}[{i}]": float(x) for i, x in enumerate(v)})
    return out


def worst_gap(got: dict, ref: dict, keep=None) -> tuple[float, str]:
    """The worst leaf's gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    med = statistics.median(ref.values())
    worst, where = 0.0, ""
    for k, r in ref.items():
        if keep is not None and k not in keep:
            continue
        gap = abs(got[k] - r) / max(r, med)
        if not np.isfinite(gap):
            return float("inf"), k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def moving_leaves(ref_grad: dict) -> set:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's. The others (a key's bias under
    softmax) move under Adam by round-off alone and are left out of the
    comparison of the parameters' change."""
    med = statistics.median(ref_grad.values())
    return {k for k, v in ref_grad.items() if v >= 1e-3 * med}


def compare_training(prog: dict, ref: dict) -> dict:
    """The numbers `correct` rests on for a training cell."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss{i}_gap"] = abs(a - b) / abs(b)
    pg, rg = flat_norms(prog["grad_norms"]), flat_norms(ref["grad_norms"])
    out["grad_gap"], out["_grad_leaf"] = worst_gap(pg, rg)
    out["grad_diff"] = reference.sketch_gap(prog["grad_sketch"],
                                            ref["grad_sketch"])
    keep = moving_leaves(rg)
    pd, rd = flat_norms(prog["delta_norms"]), flat_norms(ref["delta_norms"])
    out["delta_gap"], out["_delta_leaf"] = worst_gap(pd, rd, keep)
    out["_left_out"] = sorted(set(rg) - keep)
    return out


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip. A backend that keeps no
    such statistic (the CPU of the tests) reads 0."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
