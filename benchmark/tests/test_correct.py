"""`correct` at a toy size on the CPU: the added cells run and compare
correct; the timed path broken underneath comes out not correct, once for
each fault a cell can have; and the control (the reference in int8 put in
the program's place) comes out not correct.

These skip the harness's look for a chip (`run.main`) and drive the rest
of a run (`run.drive`): the driver, the window, the comparison with the
reference and the result line.
"""

import json

import jax
import pytest

from benchmark import manifest, peaks, run

import helpers

PEAKS = peaks.Peaks(1e12, 1e11, 1e10, "made up, for the CPU tests")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return helpers.temp_benchmark(tmp_path_factory.mktemp("bench"),
                                  fsdp4=True)


def drive(root, name, fault=None, seed=2 ** 31 + 11, seconds=1.0):
    cell = manifest.Cell(manifest.load(root), name, root)
    devices = jax.devices()[:cell.chips]
    if len(devices) < cell.chips:
        pytest.skip(f"{cell.chips} devices needed")
    line = run.drive(cell, devices, PEAKS, helpers.run_args(
        seed=seed, seconds=seconds), run.Phases(), fault=fault)
    json.dumps(line)  # the result line is JSON
    assert list(line)[-1] == "compared"  # the comparison comes last
    assert {"correct", "attempted", "failed", "metrics", "device"} <= \
        set(line)
    return line


@pytest.mark.parametrize("name,metric", [
    ("tiny-train", "train_tokens_per_s"),
    ("tiny-fsdp4", "train_tokens_per_s"),
    ("tiny-serve", "serve_tokens_per_s"),
    # the second family, found by its configuration's `model_type`
    ("toy-rope-train", "train_tokens_per_s"),
    ("toy-rope-serve", "serve_tokens_per_s"),
])
def test_added_cells_run_and_compare_correct(root, name, metric):
    # toy-rope-serve: the seed and the length its limit was read at
    line = drive(root, name, **({"seed": 7, "seconds": 3.0}
                                if name == "toy-rope-serve" else {}))
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    for c in line["compared"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("name,fault,caught_by", [
    # a step that returns its state unchanged: nothing moved
    ("tiny-train", "state_unchanged", "delta_gap"),
    # half of the batch left out, the mean taken over the rest
    ("tiny-train", "half_batch", "grad_gap"),
    # the exchange between chips left out: one chip's share alone
    ("tiny-fsdp4", "no_exchange", "grad_gap"),
    # a token altered where it is produced
    ("tiny-serve", "token_altered", "served_mean_gap"),
    # the same two faults under the second family
    ("toy-rope-train", "half_batch", "grad_gap"),
    ("toy-rope-serve", "token_altered", "served_mean_gap"),
])
def test_a_broken_timed_path_is_not_correct(root, name, fault, caught_by):
    line = drive(root, name, fault=fault)
    assert line["correct"] is False
    c = line["compared"][caught_by]
    assert c["value"] > c["limit"], line["compared"]


@pytest.mark.parametrize("name,seed", [("toy-serve", 5),
                                       ("toy-rope-serve", 7)])
def test_the_serve_control_is_not_correct(root, name, seed):
    """The engine with the program's own int8 path switched on
    (`quant="int8_fwd"`) serves tokens that lie further below the
    reference's best than the limit allows; as the cell states it
    (bf16) the same run is correct."""
    m = manifest.load(root)
    sound = drive(root, name, seed=seed, seconds=3.0)
    assert sound["correct"] is True, sound["compared"]
    cell = manifest.Cell(m, name, root)
    cell.mix["quant"] = "int8_fwd"
    line = run.drive(cell, jax.devices()[:1], PEAKS,
                     helpers.run_args(seed=seed, seconds=3.0),
                     run.Phases())
    assert line["correct"] is False
    c = line["compared"]["served_mean_gap"]
    assert c["value"] > c["limit"] > \
        sound["compared"]["served_mean_gap"]["value"]


@pytest.mark.parametrize("name", ["tiny-train", "toy-rope-train"])
def test_the_control_is_not_correct(root, name):
    """The program with its own int8 path switched on (`quant="int8"`:
    int8 matmuls forward and backward), through the same harness: the
    step below bf16 that a later PR would be tempted by has to fail."""
    cell = manifest.Cell(manifest.load(root), name, root)
    cell.mix["quant"] = "int8"
    line = run.drive(cell, jax.devices()[:1], PEAKS,
                     helpers.run_args(seed=2 ** 31 + 11),
                     run.Phases())
    assert line["correct"] is False
    c = line["compared"]["grad_diff"]
    assert c["value"] > c["limit"], line["compared"]


def test_no_accelerator_no_result(capsys):
    """`main` looks for a chip first: on this CPU it returns 3 and prints
    no result line."""
    rc = run.main(["--workload", "gpt2m-train-1chip", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == 3
    assert capsys.readouterr().out.strip() == ""
