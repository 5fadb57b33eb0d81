"""The stratified generator: the same multiset for every seed, in another
order; identical for one seed."""

import json
import pathlib

import numpy as np
import pytest

from benchmark import loadgen

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
MIXES = ["chat-saturated", "docqa-steady"]


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_multiset_every_seed(name):
    m = mix(name)
    a = loadgen.serve_trace(m, 50257, 1, 30.0)
    b = loadgen.serve_trace(m, 50257, 2 ** 31 + 5, 30.0)
    assert len(a) == len(b)
    for part in (True, False):
        xa = [r for r in a if r.in_window == part]
        xb = [r for r in b if r.in_window == part]
        assert sorted(len(r.prompt) for r in xa) == \
            sorted(len(r.prompt) for r in xb)
        assert sorted(r.max_new_tokens for r in xa) == \
            sorted(r.max_new_tokens for r in xb)
        ga = np.sort(np.diff([r.due_s for r in xa]))
        gb = np.sort(np.diff([r.due_s for r in xb]))
        # gaps between neighbours are half-sums of the permuted multiset,
        # so compare what is fixed: the count and the span they fill
        assert len(ga) == len(gb)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert [r.due_s for r in a] != [r.due_s for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_one_seed_repeats_and_fits(name):
    m = mix(name)
    a = loadgen.serve_trace(m, 50257, 12345, 30.0)
    b = loadgen.serve_trace(m, 50257, 12345, 30.0)
    assert all((x.prompt == y.prompt).all() and x.due_s == y.due_s
               and x.max_new_tokens == y.max_new_tokens
               for x, y in zip(a, b))
    dues = [r.due_s for r in a]
    assert dues == sorted(dues)
    assert all(-m["ramp_s"] <= r.due_s < 0 for r in a if not r.in_window)
    assert all(0 <= r.due_s < 30.0 for r in a if r.in_window)
    assert all(len(r.prompt) + r.max_new_tokens <= 1024 for r in a)
    n = round(m["rate_rps"] * 30.0)
    assert sum(r.in_window for r in a) == n
    assert loadgen.multiset_sizes(m, 30.0)["requests_in_window"] == n


def test_gap_multiset_is_fixed():
    spec = {"dist": "exponential"}
    a = loadgen.arrival_times(spec, 40, 0.0, 20.0,
                              np.random.default_rng(1))
    b = loadgen.arrival_times(spec, 40, 0.0, 20.0,
                              np.random.default_rng(2))
    # each arrival sits mid-gap: recover the gaps and compare multisets
    def gaps(t):
        g, edge = [], 0.0
        for x in t:
            g.append(2 * (x - edge))
            edge += g[-1]
        return np.sort(g)
    assert np.allclose(gaps(a), gaps(b))
    assert abs(gaps(a).sum() - 20.0) < 1e-9
    assert not np.allclose(a, b)


def test_stratified_values_follow_the_distribution():
    v = loadgen.stratified({"dist": "lognormal", "median": 100,
                            "sigma": 0.5}, 1001)
    assert abs(np.median(v) - 100) < 0.5
    e = loadgen.stratified({"dist": "exponential", "mean": 2.0}, 2000)
    assert abs(e.mean() - 2.0) < 0.02
    g = loadgen.stratified({"dist": "gamma_cv", "cv": 2.0, "mean": 1.0},
                           2000)
    assert abs(g.mean() - 1.0) < 1e-6 and g.std() > 1.3


def test_batch_stream_rows_differ_and_repeat():
    a = loadgen.BatchStream(50257, 8, 64, 3)
    b = loadgen.BatchStream(50257, 8, 64, 3)
    x, y, z = next(a), next(b), next(a)
    assert (x["tokens"] == y["tokens"]).all()
    assert not (x["tokens"] == z["tokens"]).all()
    assert len({r.tobytes() for r in x["tokens"]}) == 8
    assert (x["tokens"][:, 1:] == x["targets"][:, :-1]).all()
