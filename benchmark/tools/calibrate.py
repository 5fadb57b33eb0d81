"""Readings that the limits of `correct` are set from, one cell at a time.

    python3 benchmark/tools/calibrate.py --workload <cell> --seeds 12 \\
        --control-seeds 3 [--seconds 10]

One process on the chip at the cell's own size. For every seed it reads
what a run compares (the program against the f32 reference: the lower
reading is the largest of these), and for the first `--control-seeds`
seeds what the control and the planted faults read (the upper reading is
the smallest of those): the reference in int8 put in the program's
place, half of the batch left out, and on several chips one chip's share
of the batch alone (the exchange left out). Writes
`chiprun_out/calibrate/<cell>.json`.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import loadgen, manifest, run as bench_run  # noqa: E402


def train(cell, devices, seeds, n_control, witness=True):
    from benchmark.drivers import common

    drv = cell.driver

    system = drv.TrainSystem(cell, devices)
    progs, firsts = {}, {}
    for i, seed in enumerate(seeds):
        stream = loadgen.BatchStream(cell.config["vocab_size"],
                                     system.rows, system.seq, seed)
        if i == 0:
            firsts[seed] = system.start(seed, stream)
        else:
            firsts[seed] = [next(stream) for _ in range(drv.CHECK_STEPS)]
            system.install(seed)
        progs[seed] = system.first_steps(seed, firsts[seed])
        print("program", seed, progs[seed]["losses"], flush=True)
    system.free()
    rows_all = system.rows
    # the control: the program with its own int8 path switched on
    own = {}
    if n_control:
        cell.mix["quant"] = "int8"
        ctl_system = drv.TrainSystem(cell, devices)
        for i, seed in enumerate(seeds[:n_control]):
            if i == 0:
                ctl_system.start(seed, iter(firsts[seed]))
            else:
                ctl_system.install(seed)
            own[seed] = ctl_system.first_steps(seed, firsts[seed])
            print("program int8", seed, own[seed]["losses"], flush=True)
        ctl_system.free()
        cell.mix["quant"] = "none"
    out = []
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        ref = drv.reference_run(cell, devices, seed, firsts[seed])
        row = {"seed": seed, "ref_s": None,
               "program": _public(common.compare_training(progs[seed],
                                                          ref))}
        if i < n_control:
            row["control_program_int8"] = _public(
                common.compare_training(own[seed], ref))
            for mode, name in (("int8", "control_int8"),
                               ("bf16", "reference_bf16")):
                if witness:  # second witnesses; not what a limit is set from
                    got = drv.reference_run(cell, devices, seed,
                                            firsts[seed], mode=mode)
                    row[name] = _public(common.compare_training(got, ref))
            half = drv.reference_run(cell, devices, seed, firsts[seed],
                                     rows=slice(0, rows_all // 2))
            row["fault_half_batch"] = _public(
                common.compare_training(half, ref))
            if len(devices) > 1:
                share = drv.reference_run(
                    cell, devices, seed, firsts[seed],
                    rows=slice(0, rows_all // len(devices)))
                row["fault_no_exchange"] = _public(
                    common.compare_training(share, ref))
        row["ref_s"] = round(time.perf_counter() - t, 1)
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def _public(cmp: dict) -> dict:
    return {k: v for k, v in cmp.items() if not k.startswith("_")} | {
        "grad_leaf": cmp["_grad_leaf"], "delta_leaf": cmp["_delta_leaf"]}


def serve(cell, devices, seeds, n_control, seconds):
    from benchmark import reference

    drv = cell.driver
    system = drv.ServeSystem(cell, devices, seeds[0])
    kept = {}
    for i, seed in enumerate(seeds):
        if i:
            system.engine.set_params(
                system.make_params(reference.seed_u32(seed)))
            system.engine.invalidate_prefix_cache()
        trace = loadgen.serve_trace(cell.mix, cell.config["vocab_size"],
                                    seed, seconds)
        out = drv.offer(system, trace, seconds)
        system.router.run_until_idle()
        for r in out["records"]:
            if r.handle is not None and r.handle.done:
                r.finish_reason = r.handle.finish_reason
        sample = drv.sample_finished(out["records"], seed,
                                     int(cell.mix["compare_requests"]))
        kept[seed] = sample
        print("served", seed, len(sample), "requests", flush=True)
    system.close()
    # the control: the program with its own int8 path switched on, at
    # the same load, on the first seeds
    own = {}
    if n_control:
        cell.mix["quant"] = "int8_fwd"
        ctl = drv.ServeSystem(cell, devices, seeds[0])
        for i, seed in enumerate(seeds[:n_control]):
            if i:
                ctl.engine.set_params(
                    ctl.make_params(reference.seed_u32(seed)))
                ctl.engine.invalidate_prefix_cache()
            trace = loadgen.serve_trace(
                cell.mix, cell.config["vocab_size"], seed, seconds)
            out = drv.offer(ctl, trace, seconds)
            ctl.router.run_until_idle()
            for r in out["records"]:
                if r.handle is not None and r.handle.done:
                    r.finish_reason = r.handle.finish_reason
            own[seed] = drv.sample_finished(
                out["records"], seed, int(cell.mix["compare_requests"]))
        ctl.close()
        cell.mix["quant"] = "none"
    rows = []
    for seed in seeds:
        cmp = drv.served_gaps(cell, devices, seed, kept[seed])
        row = {"seed": seed, **cmp}
        if seed in own:
            row["control_program_int8"] = {
                k: v for k, v in drv.served_gaps(
                    cell, devices, seed, own[seed]).items()
                if k.startswith("served")}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=2_000_000_011)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--witness", type=int, default=1)
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
    devices = devices[:cell.chips]
    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    if cell.mix["kind"] == "train":
        rows = train(cell, devices, seeds, a.control_seeds,
                     bool(a.witness))
    else:
        rows = serve(cell, devices, seeds, a.control_seeds, a.seconds)
    out = ROOT / "chiprun_out" / "calibrate"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{a.workload}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
