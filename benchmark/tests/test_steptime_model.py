"""`tools/steptime_model.py`: the host-side model of a serve cell's
steps, against hand counts on a mix small enough to count."""

import pytest

from benchmark.tools import steptime_model

import helpers

MIX = dict(helpers.TINY_SERVE, rate_rps=1.0, ramp_s=0.0,
           arrivals={"dist": "constant", "value": 1.0},
           prompt_tokens={"dist": "constant", "value": 20},
           answer_tokens={"dist": "constant", "value": 5},
           engine={"num_slots": 2, "block_size": 16, "prefill_chunk": 16})
TIMES = dict(tick=0.010, chunk=0.020, host=0.005)


def test_an_idle_engine_delivers_what_is_offered():
    """A request a second, each over in under a tenth of one: every
    answer's 5 tokens arrive in the window, nothing is live when it
    opens, and the seed changes nothing."""
    got = [steptime_model.simulate(MIX, 320, seed, 10.0, **TIMES)
           for seed in (1, 2)]
    assert got[0] == got[1] == (pytest.approx(5.0), (0, 0))


def test_a_full_engine_delivers_what_its_steps_allow():
    """100 requests a second on two slots, a prompt two chunks and an
    answer five tokens: the queue is long when the window opens, and the
    rate lies between a step of host + chunk + tick that gives one live
    stream's token and every other step a first token, and a step of
    host + tick that gives two; the same seed gives the same run."""
    mix = dict(MIX, rate_rps=100.0, ramp_s=1.0)
    rate, (live, queued) = steptime_model.simulate(mix, 320, 3, 10.0,
                                                   **TIMES)
    assert live in (1, 2) and queued > 50
    assert 1.5 / 0.035 < rate < 2 / 0.015
    assert steptime_model.simulate(mix, 320, 3, 10.0, **TIMES)[0] == rate
