"""Traffic from a data file and a seed: one general generator.

A serve mix states distributions; the multiset of prompt lengths, of
answer lengths and of gaps between arrivals is the distribution's inverse
CDF at evenly spaced quantiles, so it is the same for every seed. The
seed only permutes each multiset and draws the token ids: every seed
offers the same number of requests, the same tokens in total and exactly
the same distribution, in another order. The ramp before the window and
the window itself are stratified apart, so the window always holds the
same requests.

A train mix states a batch shape; the seed draws a stream of batches
whose rows all differ.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified(spec: dict, n: int) -> np.ndarray:
    """The n values of `spec`'s distribution at evenly spaced quantiles."""
    q = quantiles(n)
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "exponential":
        v = -np.log1p(-q) * spec.get("mean", 1.0)
    elif dist == "gamma_cv":
        # a gamma of the stated coefficient of variation, by the
        # Wilson-Hilferty approximation of its quantiles; cv 1 is close
        # to the exponential
        k = 1.0 / spec["cv"] ** 2
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in q])
        v = k * np.maximum(1 - 1 / (9 * k) + z / (3 * math.sqrt(k)),
                           0.0) ** 3
        v = v / max(v.mean(), 1e-12) * spec.get("mean", 1.0)
    elif dist == "constant":
        v = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "min" in spec or "max" in spec:
        v = np.clip(v, spec.get("min", -np.inf), spec.get("max", np.inf))
    return v


def stratified_ints(spec: dict, n: int) -> np.ndarray:
    return np.rint(stratified(spec, n)).astype(np.int64)


def arrival_times(spec: dict, n: int, start: float, length: float,
                  rng: np.random.Generator) -> np.ndarray:
    """n arrivals inside [start, start + length): the stratified gaps,
    scaled to fill the span exactly, in the seed's order; each arrival
    sits in the middle of its own gap."""
    if n == 0:
        return np.zeros(0)
    gaps = stratified({**spec, "mean": 1.0}, n)
    gaps = gaps / gaps.sum() * length
    gaps = gaps[rng.permutation(n)]
    return start + np.cumsum(gaps) - gaps / 2


@dataclasses.dataclass
class Arrival:
    due_s: float            # relative to the window's start; < 0 in the ramp
    prompt: np.ndarray      # int32 [prompt_len]
    max_new_tokens: int
    in_window: bool


def serve_trace(mix: dict, vocab_size: int, seed: int,
                seconds: float) -> list[Arrival]:
    """The requests of one run, ramp first, in order of arrival."""
    rng = np.random.default_rng([int(seed), 0x5E12])
    rate = float(mix["rate_rps"])
    out: list[Arrival] = []
    spans = ((-float(mix["ramp_s"]), float(mix["ramp_s"]), False),
             (0.0, float(seconds), True))
    for start, length, in_window in spans:
        n = int(round(rate * length))
        times = arrival_times(mix["arrivals"], n, start, length, rng)
        prompts = stratified_ints(mix["prompt_tokens"], n)[
            rng.permutation(n)]
        answers = stratified_ints(mix["answer_tokens"], n)[
            rng.permutation(n)]
        for t, p, a in zip(times, prompts, answers):
            out.append(Arrival(
                float(t), rng.integers(0, vocab_size, int(p)).astype(
                    np.int32), int(a), in_window))
    return out


def multiset_sizes(mix: dict, seconds: float) -> dict:
    """What the log line says of the traffic: how much is offered."""
    rate = float(mix["rate_rps"])
    n_w, n_r = int(round(rate * seconds)), int(round(rate * mix["ramp_s"]))
    p = stratified_ints(mix["prompt_tokens"], n_w)
    a = stratified_ints(mix["answer_tokens"], n_w)
    return {"requests_in_window": n_w, "requests_in_ramp": n_r,
            "prompt_tokens_in_window": int(p.sum()),
            "answer_tokens_in_window": int(a.sum())}


class BatchStream:
    """A seeded stream of token batches; batch i is the same for the same
    seed, and no two rows of the stream are alike."""

    def __init__(self, vocab_size: int, rows: int, seq_len: int, seed: int):
        self._rng = np.random.default_rng([int(seed), 0x7A11])
        self._shape = (rows, seq_len + 1)
        self._vocab = vocab_size

    def __iter__(self):
        return self

    def __next__(self) -> dict:
        t = self._rng.integers(0, self._vocab, self._shape).astype(np.int32)
        return {"tokens": t[:, :-1], "targets": t[:, 1:]}
