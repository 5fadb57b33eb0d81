"""`BENCHMARK.json` and the files it names.

A cell names a configuration and a traffic mix; the harness finds
`configs/<config>.json` (through the manifest's `file`),
`traffic/<mix>.json`, the configuration's family
`families/<model_type>.py`, the mix's driver `drivers/<kind>.py` and, for
each per-layer metric, `metrics/<name>.py` by those names alone. Nothing
here knows a cell, a mix, a model or a metric by name.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
import re

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _module(path: pathlib.Path):
    """The Python file at `path` as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{path.parent.name}_" + re.sub(r"\W", "_", path.stem),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(bench: pathlib.Path, model_type: str):
    """`families/<model_type>.py`: all that knows this kind of model
    (README, "a family")."""
    return _module(bench / "families" / f"{model_type}.py")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One entry of `workloads`, resolved to its files."""

    def __init__(self, manifest: dict, name: str,
                 root: pathlib.Path = ROOT):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; it "
                           f"has {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in manifest["configs"]}[
            self.entry["config"]]
        self.config_name = cfg_entry["name"]
        with open(root / cfg_entry["file"]) as f:
            self.config = json.load(f)
        bench = root / manifest["paths"][0]
        with open(bench / "traffic" / f"{self.entry['traffic']}.json") as f:
            self.mix = json.load(f)
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if _applies(m, name)]
        self.per_layer = [m for m in manifest["per_layer"]
                          if _applies(m, name)]
        self.bench = bench

    def reader(self, metric_name: str):
        """The `read(ctx)` of `metrics/<name>.py`."""
        return _module(self.bench / "metrics" / f"{metric_name}.py").read

    @functools.cached_property
    def family(self):
        return load_family(self.bench, self.config["model_type"])

    @functools.cached_property
    def driver(self):
        """`drivers/<kind>.py` of the mix: its `run(cell, devices, args,
        phases, fault)` is the run after the look for a chip."""
        return _module(self.bench / "drivers" / f"{self.mix['kind']}.py")


def problems(manifest: dict, root: pathlib.Path = ROOT) -> list[str]:
    """What the contract would refuse, as far as it can be seen here."""
    bad: list[str] = []
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for item in manifest[group]:
            n = item["name"]
            if not NAME.match(n):
                bad.append(f"{group}: name {n!r}")
            if n in seen:
                bad.append(f"{group}: {n!r} twice")
            seen.add(n)
        if group in ("end_to_end", "per_layer"):
            if names & seen:
                bad.append(f"metric names shared: {names & seen}")
            names |= seen
    cells = {w["name"]: w for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(m["unit"]):
            bad.append(f"{m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better {m['better']!r}")
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"{m['name']}: unknown workload {w!r}")
    for m in manifest["end_to_end"]:
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound {m['bound']}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: source {m['source']!r}")
    for m in manifest["per_layer"]:
        moved = e2e.get(m["moves"])
        if moved is None:
            bad.append(f"{m['name']}: moves unknown {m['moves']!r}")
            continue
        for c in m.get("workloads", list(cells)):
            if not _applies(moved, c):
                bad.append(f"{m['name']}: cell {c} does not report "
                           f"{m['moves']}")
        if not (root / manifest["paths"][0] / "metrics"
                / f"{m['name']}.py").exists():
            bad.append(f"{m['name']}: no reader")
    for c in cells:
        n_e2e = [m for m in manifest["end_to_end"] if _applies(m, c)]
        if len(n_e2e) < 2:
            bad.append(f"{c}: needs setup_s and one more end-to-end metric")
        if not any(_applies(m, c) for m in manifest["per_layer"]):
            bad.append(f"{c}: no per-layer metric")
    bench = root / manifest["paths"][0]
    for c in manifest["configs"]:
        with open(root / c["file"]) as f:
            kind = json.load(f).get("model_type")
        if not (bench / "families" / f"{kind}.py").exists():
            bad.append(f"{c['name']}: model_type {kind!r} has no "
                       f"families/{kind}.py")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} four-chip cells of {len(cells)}")
    return bad
