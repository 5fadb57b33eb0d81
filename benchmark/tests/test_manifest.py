"""`BENCHMARK.json` as committed, and that a later PR can add to it with
new files and entries alone."""

import json
import math
import re

import pytest

from benchmark import manifest

import helpers


def test_committed_manifest_keeps_the_contract():
    m = manifest.load()
    assert manifest.problems(m) == []
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    for c in m["configs"]:
        assert (manifest.ROOT / c["file"]).exists()
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    for w in m["workloads"]:
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for group in ("end_to_end", "per_layer"):
        for metric in m[group]:
            allowed = {"name", "unit", "better", "source", "workloads"}
            allowed |= ({"bound"} if group == "end_to_end"
                        else {"layer", "moves"})
            assert set(metric) <= allowed, metric
    # no file under paths has a name the contract refuses
    for p in (manifest.ROOT / "benchmark").rglob("*"):
        rel = str(p.relative_to(manifest.ROOT))
        if "__pycache__" in rel or "/." in rel:
            continue
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    m = manifest.load()
    for w in m["workloads"]:
        cell = manifest.Cell(m, w["name"])
        mine = {e["name"] for e in cell.end_to_end}
        assert "setup_s" in mine and len(mine) >= 2
        for metric in cell.per_layer:
            assert metric["moves"] in mine, (w["name"], metric["name"])
            assert callable(cell.reader(metric["name"]))


def test_mfu_and_roofline_names():
    m = manifest.load()
    names = {p["name"]: p for p in m["per_layer"]}
    for n, p in names.items():
        if n.endswith("_roofline") or "mfu" in n:
            assert p["unit"] == "%"
    # beside every kernel roofline stands a whole-step share that moves
    # the same end-to-end metric in the same cells
    for n, p in names.items():
        if n.endswith("_roofline"):
            mfus = [q for k, q in names.items() if "mfu" in k.split(".")[0]
                    and q["moves"] == p["moves"]
                    and set(p["workloads"]) <= set(q["workloads"])]
            assert mfus, n


@pytest.mark.parametrize("name,model_type,kind", [
    ("tiny-train", "gpt2", "train"),
    # a family the benchmark did not have, from files of its own
    ("toy-rope-train", "toy_rope", "train"),
    ("toy-rope-serve", "toy_rope", "serve_open_loop"),
])
def test_a_later_pr_adds_files_and_entries_alone(tmp_path, name,
                                                 model_type, kind):
    # `temp_benchmark` itself checks that no file that was there changed
    root = helpers.temp_benchmark(tmp_path)
    m = manifest.load(root)
    assert manifest.problems(m, root) == []
    cell = manifest.Cell(m, name, root)
    assert cell.config["model_type"] == model_type
    assert cell.mix["kind"] == kind
    assert cell.family.__file__ == str(
        root / "benchmark" / "families" / f"{model_type}.py")
    assert callable(cell.driver.run)
    # the family's sixteen or ten leaves, counted from its own keys
    shapes = cell.family.shapes(cell.config)
    assert sum(math.prod(s) for s in shapes.values()) == \
        cell.family.total_params(cell.config)
    assert "dummy_count" in {p["name"] for p in cell.per_layer}

    class Ctx:
        steps = 5

    assert cell.reader("dummy_count")(Ctx()) == 5
    # and the committed cells are still what they were
    assert manifest.Cell(m, "gpt2m-train-1chip", root).config == \
        manifest.Cell(manifest.load(), "gpt2m-train-1chip").config


def test_a_configuration_without_a_family_is_named(tmp_path):
    root = helpers.temp_benchmark(tmp_path)
    cfg = root / "benchmark" / "configs" / "tiny.json"
    cfg.write_text(json.dumps(dict(helpers.TINY_CONFIG,
                                   model_type="nobody")))
    bad = manifest.problems(manifest.load(root), root)
    assert bad == ["tiny: model_type 'nobody' has no families/nobody.py"]
