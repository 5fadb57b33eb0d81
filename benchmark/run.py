"""One run of one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

One process: it loads, warms the cell's own shapes, measures for
`--seconds`, compares what the timed path produced with the plain
reference, and prints one JSON object as the last line of its standard
output. It needs a TPU that `benchmark/peaks.py` knows and as many chips
as the cell asks for; without them it exits 3 and prints no result.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import manifest, peaks  # noqa: E402

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class Phases:
    """Set-up by phase, on the host's clock from the process's start."""

    def __init__(self):
        self.marks = [("start", _PROCESS_START)]
        self.t_window = None
        self.compile_times: list = []
        self.cache_misses = 0

    def mark(self, name: str) -> None:
        self.marks.append((name, time.perf_counter()))

    def note(self, text: str) -> None:
        print(f"[benchmark] {text}", file=sys.stderr, flush=True)

    def window_start(self, t0: float) -> None:
        self.t_window = t0
        self.marks.append(("ramp", t0))
        self.misses_at_window = self.cache_misses

    def by_phase(self) -> dict:
        out = {}
        for (_, a), (name, b) in zip(self.marks, self.marks[1:]):
            out[name] = round(out.get(name, 0.0) + b - a, 3)
        return out

    def watch_compiles(self) -> None:
        import jax.monitoring

        def on_duration(event, secs, **_):
            if event == _COMPILE_EVENT:
                self.compile_times.append(time.perf_counter())

        def on_event(event, **_):
            if event == _MISS_EVENT:
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class Tracer:
    """Traces `length` seconds of the window from `after` seconds in,
    under a `bench.window` span; `span` is that span on the host's
    clock."""

    def __init__(self, out_dir: pathlib.Path):
        self.dir = out_dir
        self.state = "idle"
        self.span = None
        self._ann = None

    def poll(self, elapsed: float, at) -> None:
        import jax

        after, length = at
        if self.state == "idle" and elapsed >= after:
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
            self._t0 = time.perf_counter()
            self._stop_at = self._t0 + length
            self.state = "on"
        elif self.state == "on" and time.perf_counter() >= self._stop_at:
            self.finish()

    def finish(self) -> None:
        import jax

        if self.state != "on":
            return
        self.span = (self._t0, time.perf_counter())
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--set", action="append", default=[],
                   metavar="KEY=JSON",
                   help="override a key of the traffic mix (sweeps only)")
    return p.parse_args(argv)


def find_devices(chips: int):
    """The chips of this run, or None where JAX finds no TPU the peaks
    table knows, or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmark: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        return None, None
    try:
        pk = peaks.lookup(devs[0].device_kind)
    except KeyError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return None, None
    return devs, pk


def drive(cell, devices, pk, args, phases, fault=None,
          all_devices=None) -> dict:
    """Everything of a run after the look for a chip: the driver, the
    per-layer readers, the log line and the result line."""
    phases.watch_compiles()
    args.tracer = (Tracer(ROOT / "benchmark" / ".trace" / cell.name)
                   if args.trace else None)
    out = cell.driver.run(cell, devices, args, phases, fault=fault)
    t0, t1 = out["t0"], out["t1"]
    setup_s = phases.t_window - _PROCESS_START
    in_window = sum(1 for t in phases.compile_times if t0 <= t < t1)
    e2e = dict(out["metrics"], setup_s=setup_s)
    units = {m["name"]: m["unit"] for m in
             cell.end_to_end + cell.per_layer}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(all_devices or devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {}
    if args.trace:
        from benchmark import xtrace

        trace = xtrace.Trace.from_dir(str(args.tracer.dir))
        # what a per-layer reader may read
        ctx = types.SimpleNamespace(
            cell=cell, config=cell.config, mix=cell.mix,
            family=cell.family, peaks=pk,
            chips=len(devices), seconds=args.seconds, t0=t0, t1=t1,
            trace=trace, trace_span=args.tracer.span, e2e=e2e,
            compiles_in_window=in_window,
            compile_cache_misses=phases.misses_at_window, **out["ctx"])
        values = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                values[m["name"]] = float(v)
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        result["breakdown"] = trace.breakdown()
        shutil.rmtree(args.tracer.dir, ignore_errors=True)
    else:
        values = {m["name"]: float(e2e[m["name"]])
                  for m in cell.end_to_end if m["name"] in e2e}
    metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
    checks = {name: {"value": value, "limit": limit}
              for name, value, limit in out["checks"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and bool(checks)
    log = {"workload": cell.name, "seed": args.seed,
           "seconds": args.seconds, "setup_by_phase_s": phases.by_phase(),
           "setup_s": round(setup_s, 3), "attempted": out["attempted"],
           "failed": out["failed"], "compiles_in_window": in_window,
           "compile_cache_misses_in_setup": phases.misses_at_window,
           **out["log"]}
    print("[benchmark] run " + json.dumps(log, default=str), flush=True)
    for name, c in checks.items():
        print(f"[benchmark] compared {name} = {c['value']:.6g} "
              f"(limit {c['limit']:.6g})", file=sys.stderr, flush=True)
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device,
            **result, "compared": checks}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    cell = manifest.Cell(manifest.load(), args.workload)
    for item in args.set:
        key, _, val = item.partition("=")
        cell.mix[key] = json.loads(val)
    phases = Phases()
    from pytorchdistributed_tpu.runtime.xla_cache import (
        use_persistent_cache,
    )

    use_persistent_cache()
    import jax  # noqa: F401

    phases.mark("import")
    all_devices, pk = find_devices(cell.chips)
    if all_devices is None:
        return 3
    line = drive(cell, all_devices[:cell.chips], pk, args, phases,
                 all_devices=all_devices)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
