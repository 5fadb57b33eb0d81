"""What the arithmetic alone does to a serve cell's compared number, at
the cell's own size: a second witness beside the float32 reference.

    python3 benchmark/tools/witness.py --workload <cell> --seed <n> \\
        [--seconds 10] [--control 1]

One process on the chip. It serves the cell's traffic for the seed as a
run does, draws the run's sample of finished requests and frees the
program. Over each sampled request (prompt and served tokens, padded as
`ServeReference` pads them) the family's plain reference then runs twice:
in float32, as a run's comparison does, and in bf16 (`reference.mm` with
bf16 operands and float32 sums: the program's arithmetic and none of its
code). Read from the two:

  * `served_*`: the program's tokens against the float32 reference, what
    a run compares;
  * `witness_*`: the bf16 reference's first choice against the float32
    reference at the same positions. A program that is sound reads about
    what this witness reads; one that reads well above it is at fault;
  * where the family has `forward_choices`: on how many rows, and in how
    many entries, the two precisions chose another set of experts
    (`router_*`) or of positions (`index_*`, over queries past
    `index_topk`), and the witness's gap with and without such a row.

With `--control 1` the program's own int8 path (`quant="int8_fwd"`) is
served on the same seed and read the same way (`control`). Writes
`chiprun_out/witness/<cell>.json`. Sets no limit: `calibrate.py` does.
"""

from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import loadgen, manifest, reference  # noqa: E402
from benchmark import run as bench_run  # noqa: E402


def serve_sample(cell, devices, seed: int, seconds: float) -> list:
    """(prompt, served tokens) of the requests a run of this seed would
    compare; the engine is gone when this returns."""
    drv = cell.driver
    system = drv.ServeSystem(cell, devices, seed)
    trace = loadgen.serve_trace(cell.mix, cell.config["vocab_size"], seed,
                                seconds)
    out = drv.offer(system, trace, seconds)
    system.router.run_until_idle()
    for r in out["records"]:
        if r.handle is not None and r.handle.done:
            r.finish_reason = r.handle.finish_reason
    sample = drv.sample_finished(out["records"], seed,
                                 int(cell.mix["compare_requests"]))
    pairs = [(np.asarray(r.handle.prompt, np.int32),
              np.asarray(r.handle.tokens, np.int32)) for r in sample]
    system.close()
    return pairs


def _set_overlap(a, b):
    """[s]: how many of row t's ids in `a` [s, k] are in `b`'s row too."""
    return (a[:, :, None] == b[:, None, :]).any(-1).sum(-1)


def _mask_overlap(a, b, width: int):
    """`_set_overlap` for long rows of positions below `width`, through
    masks, a block of rows at a time."""
    s, _ = a.shape
    rows = max(r for r in range(1, min(s, 128) + 1) if s % r == 0)
    at = jnp.arange(rows)[:, None]

    def block(t0):
        ma, mb = (jnp.zeros((rows, width), bool).at[
            at, jax.lax.dynamic_slice_in_dim(t, t0, rows, 0)].set(True)
            for t in (a, b))
        return (ma & mb).sum(-1)

    return jax.lax.map(block, jnp.arange(0, s, rows)).reshape(s)


class Witness:
    """The reference in two precisions over one padded sequence, compiled
    once a precision."""

    def __init__(self, family, cfg: dict, devices):
        self.family, self.cfg = family, cfg
        self.pad_to = family.positions(cfg)
        self.choices = hasattr(family, "forward_choices")
        ref = reference.ServeReference(family, cfg, devices)
        self._load = ref.load
        self._ref = ref
        self._low = jax.jit(functools.partial(self._forward, "bf16"))
        self._read = jax.jit(self._read_fn)

    def load(self, seed: int) -> None:
        self._load(seed)

    def _forward(self, mode, params, seq):
        if self.choices:
            return self.family.forward_choices(self.cfg, params, seq, mode)
        return self.family.forward(self.cfg, params, seq[None], mode)[0], {}

    def _read_fn(self, params, seq, served, low_first, low_taps):
        ref, taps = self._forward("f32", params, seq)
        best = ref.max(-1)

        def below(tokens):
            return best - jnp.take_along_axis(
                ref, jnp.maximum(tokens, 0)[:, None], -1)[:, 0]

        pos = jnp.arange(seq.shape[0])
        out = {"served": below(served), "witness": below(low_first)}
        lo, hi = self.cfg.get("experts_held", (0, 0))
        for name, chosen in taps.items():
            k = chosen.shape[1]
            if name.endswith(".experts"):
                out[name] = k - _set_overlap(chosen, low_taps[name])
                # of the experts this chip holds, the assignments that
                # only one of the two precisions makes
                held = [jnp.where((t >= lo) & (t < hi), t, -1 - i)
                        for i, t in enumerate((chosen, low_taps[name]))]
                out[name + "_held"] = ((held[0] >= 0).sum(-1)
                                       - _set_overlap(*held))
            else:
                # a query within `index_topk` attends every position
                miss = k - _mask_overlap(chosen, low_taps[name],
                                         seq.shape[0])
                out[name] = jnp.where(pos >= k, miss, 0)
        return out

    def read(self, prompt: np.ndarray, tokens: np.ndarray) -> dict:
        """Per served token: the program's and the witness's gap, and per
        tap how many entries of the row's choice differ."""
        n, m = len(prompt), len(tokens)
        seq = np.zeros(self.pad_to, np.int32)
        seq[:n] = prompt
        seq[n:n + m - 1] = tokens[:-1]
        served = np.full(self.pad_to, -1, np.int32)
        served[n - 1:n - 1 + m] = tokens
        params, seq = self._ref.params, jnp.asarray(seq)
        low, low_taps = self._low(params, seq)
        low_first = jnp.argmax(low, -1)
        del low  # a sequence's logits: not held while the next pass runs
        got = self._read(params, seq, jnp.asarray(served), low_first,
                         low_taps)
        del low_taps
        sl = slice(n - 1, n - 1 + m)
        return {k: np.asarray(v)[sl] for k, v in got.items()}


def summarize(cfg: dict, rows: list) -> dict:
    """The sample's numbers from its requests' per-token rows."""
    cat = {k: np.concatenate([r[k] for r in rows]) for k in rows[0]}
    out = {"requests": len(rows), "tokens": int(cat["served"].size)}
    for who in ("served", "witness"):
        g = cat[who]
        out[f"{who}_mean_gap"] = float(g.mean())
        out[f"{who}_gap"] = float(g.max())
        out[f"{who}_flip_share"] = float((g > 0).mean())
    taps = [k for k in cat if k not in ("served", "witness")]
    moved = np.zeros(cat["served"].shape, bool)
    for kind, width in (("experts", cfg.get("num_experts_per_tok")),
                        ("index", cfg.get("index_topk"))):
        names = [k for k in taps if k.endswith("." + kind)]
        if not names:
            continue
        miss = np.stack([cat[k] for k in names])       # [layers, tokens]
        moved |= (miss > 0).any(0)
        who = "router" if kind == "experts" else "index"
        out[f"{who}_rows_differ_share"] = float((miss > 0).mean())
        out[f"{who}_entries_differ_share"] = float(miss.mean() / width)
    held = [k for k in taps if k.endswith("_held")]
    if held:
        out["router_held_rows_differ_share"] = float(
            (np.stack([cat[k] for k in held]) > 0).mean())
    if taps:
        # a served position whose row chose otherwise in any layer
        out["tokens_with_a_choice_moved_share"] = float(moved.mean())
        for name, sel in (("moved", moved), ("kept", ~moved)):
            out[f"witness_mean_gap_choice_{name}"] = (
                float(cat["witness"][sel].mean()) if sel.any() else None)
            out[f"served_mean_gap_choice_{name}"] = (
                float(cat["served"][sel].mean()) if sel.any() else None)
    return out


def run(cell, devices, seed: int, seconds: float, control: bool) -> dict:
    samples = {"program": serve_sample(cell, devices, seed, seconds)}
    if control:
        cell.mix["quant"] = "int8_fwd"
        samples["control"] = serve_sample(cell, devices, seed, seconds)
        cell.mix["quant"] = "none"
    wit = Witness(cell.family, cell.config, devices)
    wit.load(seed)
    out = {"seed": seed}
    for name, pairs in samples.items():
        rows = [wit.read(p, t) for p, t in pairs]
        out[name] = summarize(cell.config, rows) if rows else None
        print(json.dumps({name: out[name]}), flush=True)
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=2_000_000_011)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--control", type=int, default=0)
    p.add_argument("--set", action="append", default=[])
    a = p.parse_args()
    cell = manifest.Cell(manifest.load(), a.workload)
    for item in a.set:
        key, _, val = item.partition("=")
        cell.mix[key] = json.loads(val)
    from pytorchdistributed_tpu.runtime.xla_cache import (
        use_persistent_cache,
    )

    use_persistent_cache()
    devices, _ = bench_run.find_devices(cell.chips)
    if devices is None:
        return 3
    row = run(cell, devices[:cell.chips], a.seed, a.seconds,
              bool(a.control))
    out = ROOT / "chiprun_out" / "witness"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{a.workload}.json").write_text(json.dumps(row, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
