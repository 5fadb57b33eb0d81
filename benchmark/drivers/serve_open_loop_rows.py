"""`serve_open_loop` for a model whose whole logits do not fit beside its
reference, and whose gap no fixed number can judge. The system, the loop
and the sample are that driver's own (`ServeSystem`, `offer`,
`sample_finished`, imported from it); the comparison after the window is
this file's, and differs in two things.

The reference gives the logits of the positions that served a token alone
(`family.forward(..., rows=...)`): whole float32 logits of 16,384
positions over a vocabulary of 151,936 are 9.96 GB, which one chip does
not have beside 7.9 GB of weights, and a request serves at most a thousand
tokens. What is computed of them is `reference.ServeReference`'s: for
every served token, by how much its float32 reference logit lies below the
reference's best, and the same for the int8 control's first choice.

And what decides `correct` is `served_over_control`: the served tokens'
mean gap over the mean gap of the int8 reference control **on the same
prompts and tokens of the same run**. A model whose experts are chosen by
the largest of 64 logits has seeds (each draws its own weights) whose
streams hold ten times the near-ties of others, so the program's mean gap
and the control's each range tenfold over seeds and the two ranges touch,
while on every one seed the control reads several times the program. The
control reads 1 by construction; the program's own int8 path reads a
little over 1 (the readings: `PERF.md`, section 4). `run` below is
`serve_open_loop.run` with those two differences and the checks taken by
the names the mix's `limits` give; a `benchmark` PR that moves `rows` and
the ratio into `reference.py` and the shared driver deletes this file
(`PERF.md`, section 7).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import loadgen, reference, window
from benchmark.drivers import common
from benchmark.drivers.serve_open_loop import (  # noqa: F401
    ServeSystem,
    offer,
    plant_altered_token,
    sample_finished,
)

#: rows of logits one compiled comparison reads (more where a request
#: served more: the next power of two)
ROWS = 1024


class ServedRowsReference(reference.ServeReference):
    """`ServeReference` with the head applied to the served positions
    alone: the forward pass is the whole padded sequence's, the logits are
    those of `rows` consecutive positions from the prompt's last on."""

    def __init__(self, family, cfg: dict, devices):
        super().__init__(family, cfg, devices)
        self._gaps = jax.jit(self._gaps_fn, static_argnames="rows")

    def _gaps_fn(self, params, seq, served, first, rows: int):
        at = first + jnp.arange(rows)
        inside = at < self.pad_to
        at = jnp.minimum(at, self.pad_to - 1)
        ref, low = (self.family.forward(self.cfg, params, seq[None], mode,
                                        rows=at)[0]
                    for mode in ("f32", "int8"))
        served = jnp.where(inside, served[at], -1)
        best = ref.max(-1)
        pick = jnp.take_along_axis(
            ref, jnp.maximum(served, 0)[:, None], -1)[:, 0]
        ctl = jnp.take_along_axis(
            ref, jnp.argmax(low, -1)[:, None], -1)[:, 0]
        live = served >= 0
        return (jnp.where(live, best - pick, 0.0),
                jnp.where(live, best - ctl, 0.0))

    def gaps(self, prompt: np.ndarray, tokens: np.ndarray):
        """(program's gaps, control's gaps), one per served token."""
        n, m = len(prompt), len(tokens)
        seq = np.zeros(self.pad_to, np.int32)
        seq[:n] = prompt
        seq[n:n + m - 1] = tokens[:-1]
        served = np.full(self.pad_to, -1, np.int32)
        served[n - 1:n - 1 + m] = tokens
        g, c = self._gaps(self.params, jnp.asarray(seq),
                          jnp.asarray(served), jnp.int32(n - 1),
                          rows=max(ROWS, 1 << (m - 1).bit_length()))
        return np.asarray(g)[:m], np.asarray(c)[:m]


def served_gaps(cell, devices, seed: int, sample: list) -> dict:
    """`serve_open_loop.served_gaps` through `ServedRowsReference`, and
    `served_over_control`: the served tokens' mean gap over the int8
    reference control's on the same prompts and tokens (infinite where
    the control never left the reference's first choice)."""
    ref = ServedRowsReference(cell.family, cell.config, devices)
    ref.load(seed)
    gaps = [ref.gaps(np.asarray(r.handle.prompt),
                     np.asarray(r.handle.tokens, np.int32))
            for r in sample]
    g = np.concatenate([got for got, _ in gaps])
    c = np.concatenate([ctl for _, ctl in gaps])
    return {"served_gap": float(g.max()),
            "served_mean_gap": float(g.mean()),
            "served_flip_share": float((g > 0).mean()),
            "control_gap": float(c.max()),
            "control_mean_gap": float(c.mean()),
            "control_flip_share": float((c > 0).mean()),
            "served_over_control": (float(g.mean() / c.mean())
                                    if c.mean() > 0 else float("inf")),
            "tokens": int(g.size), "requests": len(sample)}


def run(cell, devices, args, phases, fault=None) -> dict:
    mix = cell.mix
    system = ServeSystem(cell, devices, args.seed, phases)
    trace = loadgen.serve_trace(mix, cell.config["vocab_size"], args.seed,
                                args.seconds)
    trace_at = None
    if args.trace:
        trace_at = (args.seconds - float(mix["trace_s"]),
                    float(mix["trace_s"]))
    if fault == "token_altered":
        plant_altered_token(system, cell.config["vocab_size"])
    out = offer(system, trace, args.seconds, tracer=args.tracer,
                trace_at=trace_at, phases=phases)
    records, t0, t1 = out["records"], out["t0"], out["t1"]
    timeout = float(mix["first_token_timeout_s"])
    metrics = window.serve_metrics(records, t0, t1, timeout)
    peak = common.memory_peak_bytes(devices)
    pool_bytes = int(system.engine.kv_hbm_bytes)
    system.close()
    phases.note("window closed; running the reference")
    sample = sample_finished(records, args.seed,
                             int(mix["compare_requests"]))
    in_win = [r for r in records if r.in_window]
    # failed: as `serve_open_loop.run` counts them
    bad = [r for r in in_win if r.sent is None
           or r.finish_reason not in (None, "length", "stop")
           or (mix.get("drain", False) and not r.token_times)]
    if sample:
        cmp = served_gaps(cell, devices, args.seed, sample)
        checks = [(k, cmp[k], v) for k, v in mix["limits"].items()]
    else:  # nothing finished: nothing shown to be right
        cmp = {"tokens": 0, "requests": 0}
        checks = [(k, float("inf"), v) for k, v in mix["limits"].items()]
    sizes = loadgen.multiset_sizes(mix, args.seconds)
    return {
        "metrics": metrics, "checks": checks, "attempted": len(in_win),
        "failed": len(bad), "memory_peak_bytes": int(peak),
        "t0": t0, "t1": t1,
        "log": {"rate_rps": mix["rate_rps"], **sizes,
                "slots": mix["engine"]["num_slots"],
                "kv_pool_bytes": pool_bytes,
                "finished": sum(1 for r in records if r.finish_reason),
                "first_tokens_in_window": sum(
                    1 for r in in_win if r.token_times),
                "gen_lateness_p95_ms": window.lateness_p95_ms(records),
                "compared_requests": cmp["requests"],
                "compared_tokens": cmp["tokens"],
                **{k: cmp.get(k) for k in (
                    "served_gap", "served_mean_gap", "served_flip_share",
                    "control_gap", "control_mean_gap",
                    "control_flip_share", "served_over_control")},
                **{k: v for k, v in out["counters"].items()
                   if k != "engine"}},
        "ctx": {"records": records, "counters": out["counters"]},
    }
