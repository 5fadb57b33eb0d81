"""Offered rates against one serve cell, in one process on the chip.

    python3 benchmark/tools/sweep.py --workload <cell> --rates 0.5,1,1.5 \\
        --seeds 3 --seconds 30

Builds the cell's router and engine once and runs ramp + window for every
rate and seed. Prints, for each run, the end-to-end metrics, the growth
of the backlog over the window and the median TTFT of the window's two
halves (the knee is the highest rate at which the backlog does not grow
and the second half is no slower than the first), and at the end each
rate's medians and spreads. Writes `chiprun_out/sweep/<cell>.json`.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import loadgen, manifest, run as bench_run, window  # noqa: E402
from benchmark.tools.spread import spread  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=2_100_000_023)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--set", action="append", default=[])
    p.add_argument("--tag", default="")
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
    drv = cell.driver
    system = drv.ServeSystem(cell, devices[:1], a.first_seed)
    rows = []
    timeout = float(cell.mix["first_token_timeout_s"])
    for rate in [float(x) for x in a.rates.split(",")]:
        cell.mix["rate_rps"] = rate
        for i in range(a.seeds):
            seed = a.first_seed + 104729 * i
            trace = loadgen.serve_trace(
                cell.mix, cell.config["vocab_size"], seed, a.seconds)
            out = drv.offer(system, trace, a.seconds)
            t0, t1 = out["t0"], out["t1"]
            m = window.serve_metrics(out["records"], t0, t1, timeout)
            mid = (t0 + t1) / 2
            halves = []
            for lo, hi in ((t0, mid), (mid, t1)):
                tt = [(r.token_times[0] - r.due) if r.token_times
                      else timeout for r in out["records"]
                      if r.in_window and lo <= r.due < hi]
                halves.append(window.percentile(tt, 50) * 1e3
                              if tt else None)
            c = out["counters"]
            row = {"rate": rate, "seed": seed, **m,
                   "backlog_growth_rps":
                       (c["queued_t1"] - c["queued_t0"]) / a.seconds,
                   "queued_t0": c["queued_t0"],
                   "active_t0": c["active_t0"],
                   "queued_t1": c["queued_t1"],
                   "active_t1": c["active_t1"],
                   "ttft_p50_halves_ms": halves,
                   "occupancy": c["engine"].get("slot_occupancy"),
                   "lateness_p95_ms":
                       window.lateness_p95_ms(out["records"])}
            print(json.dumps(row), flush=True)
            rows.append(row)
            system.router.run_until_idle()
    system.close()
    summary = []
    for rate in sorted({r["rate"] for r in rows}):
        rs = [r for r in rows if r["rate"] == rate]
        s = {"rate": rate, "runs": len(rs)}
        for k in ("serve_tokens_per_s", "itl_p95_ms", "ttft_p50_ms",
                  "ttft_p95_ms", "backlog_growth_rps"):
            v = [r[k] for r in rs if r.get(k) is not None]
            if v:
                s[k] = statistics.median(v)
                if len(v) >= 3 and k != "backlog_growth_rps":
                    s[k + "_spread"] = spread(v)
        print("SUMMARY " + json.dumps(s), flush=True)
        summary.append(s)
    out = ROOT / "chiprun_out" / "sweep"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{a.workload}{a.tag}.json").write_text(
        json.dumps({"runs": rows, "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
