"""The reduction from a profiler trace (`*.xplane.pb`) to numbers.

What the trace of a TPU run holds (looked at by hand, PR 26): one plane
`/device:TPU:<n>` per chip with the lines `XLA Modules` (one event per
execution of a compiled program, named `jit_<fn>(<hash>)`), `XLA Ops` (one
event per HLO instruction executed, named by its full HLO text, `%name.N =
...`; a `while` contains its body's events) and `Async XLA Ops` (the span
from a `-start` to its `-done`); and one plane `/host:CPU` whose lines are
threads, holding the `TraceAnnotation` spans of the benchmark.

An instruction's name begins with the innermost flax scope that produced
it where XLA kept one (`%attn.12` is the Pallas call under `attn`), so
`scope_time` matches on that prefix. Times are nanoseconds since the
trace began, on one clock for host and device.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"all-to-all)")
WINDOW_SPAN = "bench.window"


def span_prefixes() -> tuple:
    """Prefixes of the host spans the reduction keeps: the lines of every
    `spans/*.txt` beside this file (a PR that adds spans adds a file)."""
    out = [WINDOW_SPAN]
    for path in sorted(glob.glob(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "spans", "*.txt"))):
        with open(path) as f:
            out += [ln.strip() for ln in f
                    if ln.strip() and not ln.startswith("#")]
    return tuple(out)


def op_name(event_name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def family(event_name: str) -> str:
    """... -> `fusion`: the name without its instruction number."""
    return re.sub(r"[.\d]+$", "", op_name(event_name))


def union_length(intervals) -> float:
    """Total length covered by [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def subtract(a, b) -> list:
    """The parts of merged intervals `a` that no interval of `b` covers."""
    out, b = [], merged(b)
    j = 0
    for s, e in merged(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Event:
    name: str
    start: float   # ns
    dur: float     # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class DevicePlane:
    name: str
    modules: list
    ops: list
    async_ops: list


class Trace:
    """One capture, reduced on demand. `window` is the span of the
    benchmark's own `bench.window` annotation when the capture has one,
    else the whole capture."""

    def __init__(self, devices: list[DevicePlane], host: list[Event]):
        self.devices = devices
        self.host = host
        spans = [e for e in host if e.name == WINDOW_SPAN]
        if spans:
            self.window = (spans[0].start, spans[0].end)
        else:
            every = [e for d in devices for e in d.ops] + host
            self.window = (min(e.start for e in every),
                           max(e.end for e in every))

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Trace":
        paths = sorted(glob.glob(os.path.join(trace_dir, "**",
                                              "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not paths:
            raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
        return cls.from_file(paths[-1])

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        devices, host = [], []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                lines = {ln.name: ln for ln in plane.lines}

                def events(key):
                    ln = lines.get(key)
                    return [] if ln is None else [
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in ln.events]

                devices.append(DevicePlane(
                    plane.name, events("XLA Modules"), events("XLA Ops"),
                    events("Async XLA Ops")))
            elif plane.name == "/host:CPU":
                keep = span_prefixes()
                for ln in plane.lines:
                    host.extend(Event(e.name, e.start_ns, e.duration_ns)
                                for e in ln.events
                                if e.name.startswith(keep))
        return cls(devices, host)

    # -- the window -------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran on the device, averaged over
        the devices that ran any."""
        lo, hi = self.window
        busy = [union_length(clip([(e.start, e.end) for e in d.ops],
                                  lo, hi)) / 1e9
                for d in self.devices if d.ops]
        if not busy:
            raise ValueError("no device operation in the trace")
        return sum(busy) / len(busy)

    def _in_window(self, events):
        lo, hi = self.window
        return [e for e in events if e.start >= lo and e.end <= hi]

    # -- programs and operations ------------------------------------------

    def program_runs(self, prefix: str) -> list[Event]:
        """Executions of the compiled program `jit_<prefix>...` that lie
        wholly inside the window, on the first device."""
        return [e for e in self._in_window(self.devices[0].modules)
                if e.name.startswith(prefix)]

    def leaf_ops(self, device: int = 0) -> list[Event]:
        """Operations that contain no other (a `while` is its body)."""
        ops = sorted(self.devices[device].ops,
                     key=lambda e: (e.start, -e.dur))
        out = []
        for e, nxt in zip(ops, ops[1:] + [None]):
            if nxt is None or nxt.start >= e.end:
                out.append(e)
        return out

    def self_times(self, device: int = 0) -> dict:
        """Seconds by operation family inside the window, a container's
        time less its children's."""
        lo, hi = self.window
        ops = sorted((e for e in self.devices[device].ops
                      if e.end > lo and e.start < hi),
                     key=lambda e: (e.start, -e.dur))
        out: dict = {}
        stack: list = []
        for e in ops:
            while stack and e.start >= stack[-1].end:
                stack.pop()
            if stack:
                fam = family(stack[-1].name)
                out[fam] = out.get(fam, 0.0) - e.dur / 1e9
            fam = family(e.name)
            out[fam] = out.get(fam, 0.0) + e.dur / 1e9
            stack.append(e)
        return out

    def scope_time(self, scope: str, inside: list[Event] | None = None,
                   device: int = 0) -> float:
        """Seconds of the operations named after `scope` (`%attn.7`),
        optionally only those inside the given program runs."""
        ops = [e for e in self._in_window(self.devices[device].ops)
               if re.match(rf"^{re.escape(scope)}[.\d]*$", op_name(e.name))]
        if inside is not None:
            spans = merged([(r.start, r.end) for r in inside])
            ops = [e for e in ops
                   if any(s <= e.start and e.end <= t for s, t in spans)]
        return sum(e.dur for e in ops) / 1e9

    def collective_exposed_s(self, device: int = 0) -> float:
        """Seconds inside the window in which a collective was in flight
        on the device and nothing else ran there."""
        lo, hi = self.window
        d = self.devices[device]
        coll = [(e.start, e.end) for e in d.ops + d.async_ops
                if COLLECTIVE.match(op_name(e.name))]
        other = [(e.start, e.end) for e in self.leaf_ops(device)
                 if not COLLECTIVE.match(op_name(e.name))]
        return union_length(subtract(clip(coll, lo, hi),
                                     clip(other, lo, hi))) / 1e9

    # -- the breakdown ----------------------------------------------------

    def idle_gaps(self, device: int = 0) -> list:
        """[(host span name, seconds)]: the device's idle time inside the
        window, by the innermost benchmark span that covers each gap's
        middle."""
        lo, hi = self.window
        busy = merged(clip([(e.start, e.end)
                            for e in self.devices[device].ops], lo, hi))
        gaps = subtract([(lo, hi)], busy)
        spans = sorted((e for e in self.host if e.name != WINDOW_SPAN),
                       key=lambda e: e.dur)
        out: dict = {}
        for s, e in gaps:
            mid = (s + e) / 2
            name = next((h.name for h in spans if h.start <= mid < h.end),
                        "(no span)")
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
        return sorted(out.items(), key=lambda kv: -kv[1])

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.self_times().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:top]]}
