"""A step-time model of a serve cell, on the host, with no chip: how far
do the seed's arrivals and lengths alone move `serve_tokens_per_s`?

    python3 benchmark/tools/steptime_model.py --workload <cell> \\
        --tick-ms 37 --chunk-ms 47 --host-ms 6.2 [--seeds 48] \\
        [--set KEY=JSON ...]

Walks the cell's own arrivals (`loadgen.serve_trace`) through a scheduler
of the engine's shape: `num_slots` slots, one prefill lane that carries
one chunk a step, a step of host + chunk (where one is carried) + tick
(where a stream is live), a token a live stream a tick. The three times
are read off a traced run of the cell and given by hand. Prints the
median and the deviation of the tokens a second over the seeds, the
spread of each set of six, and how many slots are live when the window
opens. A `--set` changes a key of the mix (a nested one with a JSON
object, merged): what another mix would read, by this model.

It is a model: it knows no eviction, no preemption, no probe, and no
step whose time depends on the contexts. What it says of a mix that was
never run on the chip is unverified.
"""

from __future__ import annotations

import argparse
import collections
import json
import pathlib
import statistics
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from benchmark import loadgen, manifest  # noqa: E402
from benchmark.tools.spread import spread  # noqa: E402


def simulate(mix, vocab: int, seed: int, seconds: float, *, tick: float,
             chunk: float, host: float):
    """(tokens a second delivered in the window, slots live and requests
    queued when it opens) of one seed's arrivals."""
    slots = int(mix["engine"]["num_slots"])
    csize = int(mix["engine"]["prefill_chunk"])
    arr = [(a.due_s, len(a.prompt), a.max_new_tokens)
           for a in loadgen.serve_trace(mix, vocab, seed, seconds)]
    t, nxt, tokens = -float(mix["ramp_s"]), 0, 0
    queue, active, pre = collections.deque(), [], None
    at_open = None
    while t < seconds:
        while nxt < len(arr) and arr[nxt][0] <= t:
            queue.append(arr[nxt])
            nxt += 1
        if at_open is None and t >= 0:
            at_open = (len(active), len(queue))
        if pre is None and queue and len(active) < slots:
            _, p, m = queue.popleft()
            pre = [-(-p // csize), m]           # chunks left, answer
        if pre is None and not active:          # nothing to do: wait
            t = min(arr[nxt][0] if nxt < len(arr) else seconds,
                    seconds) + 1e-9
            continue
        t += host + (chunk if pre else 0.0) + (tick if active else 0.0)
        inside = 0 <= t < seconds
        if active:
            tokens += len(active) * inside
            active = [r - 1 for r in active if r > 1]
        if pre:
            pre[0] -= 1
            if not pre[0]:                      # the prompt's first token
                tokens += inside
                if pre[1] > 1:
                    active.append(pre[1] - 1)
                pre = None
    return tokens / seconds, at_open or (len(active), len(queue))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--tick-ms", type=float, required=True)
    p.add_argument("--chunk-ms", type=float, required=True)
    p.add_argument("--host-ms", type=float, required=True)
    p.add_argument("--seeds", type=int, default=48)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--set", action="append", default=[])
    a = p.parse_args()
    m = manifest.load()
    cell = manifest.Cell(m, a.workload)
    mix = cell.mix
    for item in a.set:
        key, _, val = item.partition("=")
        val = json.loads(val)
        if isinstance(val, dict) and isinstance(mix.get(key), dict):
            mix[key] = {**mix[key], **val}
        else:
            mix[key] = val
    seconds = a.seconds or float(m["run_seconds"])
    seeds = [int(x) for x in
             np.random.default_rng(0).integers(1, 2**31, a.seeds)]
    runs = [simulate(mix, cell.config["vocab_size"], s, seconds,
                     tick=a.tick_ms / 1e3, chunk=a.chunk_ms / 1e3,
                     host=a.host_ms / 1e3) for s in seeds]
    rate = [r[0] for r in runs]
    live = sorted(r[1][0] for r in runs)
    print(json.dumps({
        "workload": a.workload, "set": a.set, "seeds": len(seeds),
        "tokens_per_s_median": round(statistics.median(rate), 1),
        "tokens_per_s_sd_share": round(
            statistics.pstdev(rate) / statistics.fmean(rate), 4),
        "spread_of_sets_of_six": [round(spread(rate[i:i + 6]), 4)
                                  for i in range(0, len(rate) - 5, 6)],
        "active_t0_min_median_max": [live[0], live[len(live) // 2],
                                     live[-1]],
        "queued_t0_max": max(r[1][1] for r in runs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
