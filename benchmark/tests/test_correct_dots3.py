"""`correct` for the `dots3_note` family at a toy size on the CPU, as
`test_correct.py` has it for the two families there: the toy cell runs
through the harness and compares correct; the control (the engine with
the program's own int8 path, which the latent projections and the experts
honour) and a token altered where it is produced come out not correct.

The cell is added to a temporary copy of the benchmark as new files and
entries (`helpers.temp_benchmark`, then one configuration, one mix and
one cell more); the family file is the repository's own.
"""

import json

import jax
import pytest

from benchmark import manifest, run
from benchmark.tools import witness

import helpers
from test_correct import PEAKS, drive

# the shape of dots3-note-prev (a dense layer, then full, sliding,
# sliding, sliding; half of 16 experts held, top-4, one shared), wide
# enough that the program's int8 path reads apart from its bf16 path in
# the served tokens; contexts pass `index_topk` and the window
TOY_DOTS3_CONFIG = {
    "model_type": "dots3_note", "hidden_size": 256,
    "num_hidden_layers": 5,
    "layer_types": ["full_attention", "full_attention",
                    "sliding_attention", "sliding_attention",
                    "sliding_attention"],
    "first_k_dense_replace": 1, "intermediate_size": 512,
    "num_attention_heads": 4, "q_lora_rank": 64, "kv_lora_rank": 32,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "rope_theta": 80000000, "index_n_heads": 4, "index_head_dim": 32,
    "index_topk": 32, "swa_num_attention_heads": 2, "swa_q_lora_rank": 64,
    "swa_kv_lora_rank": 64, "swa_qk_nope_head_dim": 48,
    "swa_qk_rope_head_dim": 16, "swa_v_head_dim": 32,
    "swa_rope_theta": 50000, "sliding_window_size": 33,
    "apply_mla_qkv_lora_rescale": True, "moe_intermediate_size": 128,
    "n_routed_experts": 8, "published_n_routed_experts": 16,
    "experts_held": [0, 8], "n_shared_experts": 1,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "rms_norm_eps": 1e-5, "vocab_size": 8192,
    "max_position_embeddings": 256, "served_positions": 256,
    "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
    "initializer_range": 0.02, "router_init_std": 0.09,
    "source": "a toy for the CPU tests; no published model"}
TOY_DOTS3_SERVE = dict(
    helpers.TOY_SERVE,
    engine={"num_slots": 4, "block_size": 16, "prefill_chunk": 32,
            "prefix_cache": False},
    limits={"served_mean_gap": 2.0e-3})
SEED, SECONDS = 7, 3.0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = helpers.temp_benchmark(tmp_path_factory.mktemp("bench"))
    b = tmp / "benchmark"
    (b / "configs" / "toy-dots3.json").write_text(
        json.dumps(TOY_DOTS3_CONFIG))
    (b / "traffic" / "toy-dots3-serve.json").write_text(
        json.dumps(TOY_DOTS3_SERVE))
    m = json.loads((tmp / "BENCHMARK.json").read_text())
    m["configs"].append({
        "name": "toy-dots3", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/toy-dots3.json", "reduced": [],
        "why": "a toy of the dots3_note family"})
    m["workloads"].append({"name": "toy-dots3-serve",
                           "config": "toy-dots3",
                           "traffic": "toy-dots3-serve", "chips": 1,
                           "why": "toy"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if metric["name"] in ("itl_p95_ms", "serve_tokens_per_s",
                              "dummy_count"):
            metric["workloads"].append("toy-dots3-serve")
    (tmp / "BENCHMARK.json").write_text(json.dumps(m))
    assert manifest.problems(m, tmp) == []
    return tmp


def test_the_toy_cell_runs_and_compares_correct(root):
    line = drive(root, "toy-dots3-serve", seed=SEED, seconds=SECONDS)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["itl_p95_ms"]["value"] > 0


def test_a_token_altered_is_not_correct(root):
    line = drive(root, "toy-dots3-serve", fault="token_altered",
                 seed=SEED, seconds=SECONDS)
    assert line["correct"] is False
    c = line["compared"]["served_mean_gap"]
    assert c["value"] > c["limit"], line["compared"]


def test_the_serve_control_is_not_correct(root):
    """`quant="int8_fwd"`: every stored matrix of the latent layers, the
    shared and dense feed-forwards and the head through the int8
    contraction, the experts' grouped product on int8-rounded operands."""
    cell = manifest.Cell(manifest.load(root), "toy-dots3-serve", root)
    cell.mix["quant"] = "int8_fwd"
    line = run.drive(cell, jax.devices()[:1], PEAKS,
                     helpers.run_args(seed=SEED, seconds=SECONDS),
                     run.Phases())
    assert line["correct"] is False
    c = line["compared"]["served_mean_gap"]
    assert c["value"] > c["limit"], line["compared"]


def test_the_bf16_witness_sides_with_the_program_and_not_the_control(root):
    """`tools/witness.py` at the toy size: the reference in bf16 reads a
    gap of the program's order against the float32 reference, the
    program's int8 path reads over the limit, and the two precisions'
    choices of experts and positions are counted."""
    cell = manifest.Cell(manifest.load(root), "toy-dots3-serve", root)
    out = witness.run(cell, jax.devices()[:1], SEED, SECONDS, control=True)
    limit = cell.mix["limits"]["served_mean_gap"]
    prog, ctl = out["program"], out["control"]
    assert prog["requests"] == cell.mix["compare_requests"]
    assert prog["served_mean_gap"] < limit < ctl["served_mean_gap"]
    assert 0 < prog["witness_mean_gap"] < limit
    assert prog["served_mean_gap"] < 3 * prog["witness_mean_gap"] + 1e-4
    for k in ("router_rows_differ_share", "router_entries_differ_share",
              "index_rows_differ_share", "index_entries_differ_share",
              "router_held_rows_differ_share",
              "tokens_with_a_choice_moved_share"):
        assert 0 <= prog[k] <= 1, k
    assert prog["router_entries_differ_share"] <= prog[
        "router_rows_differ_share"]
