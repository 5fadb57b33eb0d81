"""What both drivers share: the adapter between the benchmark's weight
layout and the program's parameter tree, and the comparison of norms.

The benchmark makes the weights (`reference.make_weights`) and hands them
to the program in the tree its model expects, as a loader of a published
checkpoint would. This file is the only place that knows that tree.
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference


def to_program_tree(w: dict, cfg: dict, scan_layers: bool) -> dict:
    """Benchmark layout (stacked by layer) -> `GPT2`'s `params` tree."""
    e, l = cfg["n_embd"], cfg["n_layer"]
    block = {
        "attn": {"qkv_kernel": w["qkv_w"].reshape(l, e, 3, e),
                 "qkv_bias": w["qkv_b"].reshape(l, 3, e),
                 "out": {"kernel": w["proj_w"], "bias": w["proj_b"]}},
        "ln1": {"scale": w["ln1_g"], "bias": w["ln1_b"]},
        "ln2": {"scale": w["ln2_g"], "bias": w["ln2_b"]},
        "mlp": {"wi": {"kernel": w["fc_w"], "bias": w["fc_b"]},
                "wo": {"kernel": w["out_w"], "bias": w["out_b"]}},
    }
    if scan_layers:
        h = {"block": block}
    else:
        h = {f"block_{i}": jax.tree.map(lambda x, i=i: x[i], block)
             for i in range(l)}
    return {"params": {
        "embed": {"tok": {"embedding": w["wte"]}, "pos": w["wpe"]},
        "h": h,
        "ln_f": {"scale": w["lnf_g"], "bias": w["lnf_b"]}}}


def from_program_tree(tree: dict, cfg: dict, scan_layers: bool) -> dict:
    """The inverse, for reading gradients and changes back."""
    e, l = cfg["n_embd"], cfg["n_layer"]
    p = tree["params"] if "params" in tree else tree
    if scan_layers:
        block = p["h"]["block"]
    else:
        block = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[p["h"][f"block_{i}"] for i in range(l)])
    return {
        "wte": p["embed"]["tok"]["embedding"], "wpe": p["embed"]["pos"],
        "ln1_g": block["ln1"]["scale"], "ln1_b": block["ln1"]["bias"],
        "qkv_w": block["attn"]["qkv_kernel"].reshape(l, e, 3 * e),
        "qkv_b": block["attn"]["qkv_bias"].reshape(l, 3 * e),
        "proj_w": block["attn"]["out"]["kernel"],
        "proj_b": block["attn"]["out"]["bias"],
        "ln2_g": block["ln2"]["scale"], "ln2_b": block["ln2"]["bias"],
        "fc_w": block["mlp"]["wi"]["kernel"],
        "fc_b": block["mlp"]["wi"]["bias"],
        "out_w": block["mlp"]["wo"]["kernel"],
        "out_b": block["mlp"]["wo"]["bias"],
        "lnf_g": p["ln_f"]["scale"], "lnf_b": p["ln_f"]["bias"],
    }


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
