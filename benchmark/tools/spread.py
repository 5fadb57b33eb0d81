"""Medians and spreads of the runs `sets.sh` made.

    python3 benchmark/tools/spread.py chiprun_out/sets/<cell>.jsonl

A spread is the distance between the first and the third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median. Prints,
for each end-to-end metric, each set's median and spread, and the bound
that five times the wider spread gives.
"""

import json
import statistics
import sys


def spread(values):
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(path: str) -> int:
    rows = [json.loads(ln) for ln in open(path) if ln.strip()]
    bad = [r for r in rows if r["rc"] != 0 or not r["line"]["correct"]]
    print(f"{len(rows)} runs, {len(bad)} not correct or failed:",
          [(r["seed"], r["rc"]) for r in bad])
    metrics = sorted({k for r in rows if r["set"] for k in
                      r["line"]["metrics"]})
    for m in metrics:
        per_set = {}
        for s in (1, 2):
            per_set[s] = [r["line"]["metrics"][m]["value"] for r in rows
                          if r["set"] == s and m in r["line"]["metrics"]]
        # the first run of the first set compiles: its set-up stands apart
        if m == "setup_s" and per_set[1]:
            print(f"  setup_s first (cold) run: {per_set[1][0]:.2f}")
            per_set[1] = per_set[1][1:]
        line = f"  {m}:"
        spreads = []
        for s, v in per_set.items():
            if v:
                spreads.append(spread(v))
                line += (f" set{s} median {statistics.median(v):.6g} "
                         f"spread {100 * spreads[-1]:.3f}% (n={len(v)});")
        if spreads:
            line += f" 5x wider = {500 * max(spreads):.2f}%"
        print(line)
    traced = [r for r in rows if r["set"] == 0]
    for r in traced:
        ln = r["line"]
        d = ln["device"]
        print("  traced", r["seed"], "correct", ln["correct"], "idle",
              round(1 - d["busy_s"] / d["window_s"], 4),
              {k: round(v["value"], 4) for k, v in ln["metrics"].items()})
    if rows:
        d = rows[-1]["line"]["device"]
        print("  memory_peak_bytes", d["memory_peak_bytes"])
        print("  compared (max over runs):", {
            k: max(r["line"]["compared"][k]["value"] for r in rows
                   if k in r["line"].get("compared", {}))
            for k in rows[-1]["line"].get("compared", {})})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
