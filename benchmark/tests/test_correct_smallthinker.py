"""`correct` for the `smallthinker` family at a toy size on the CPU, as
`test_correct_evabyte.py` has it for the family there: the toy cell runs
through the harness (the `serve_open_loop_rows` driver, whose reference
gives the logits of the served positions alone) and compares correct; the
control (the engine with the program's own int8 path) comes out not
correct, and so does each fault planted in what this family brought to
the program: RoPE in a full layer, none in a window layer, the router fed
the stream after attention, the window one position short. The family's
counts are pinned at the published widths, and its readers read or return
`None`.

The cells are added to a temporary copy of the benchmark as new files and
entries (`helpers.temp_benchmark`, then configurations, one mix and cells
more); the family file and the driver are the repository's own.
"""

import dataclasses
import json
import types

import jax
import numpy as np
import pytest

from benchmark import manifest, peaks, reference, run

import helpers
from test_correct import PEAKS, drive

# SmallThinker's shape (a full NoPE layer then three RoPE window layers,
# grouped heads whose size is not the width's share, softmax-routed ReGLU
# experts routed before attention), wide enough that the program's int8
# path reads apart from its bf16 path in the served tokens; prompts and
# answers cross one to three windows
TOY_ST_CONFIG = {
    "model_type": "smallthinker", "hidden_size": 256, "head_dim": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 8, "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "sliding_window_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "sliding_window_size": 32, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 2, "moe_ffn_hidden_size": 128,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1500000, "vocab_size": 512,
    "max_position_embeddings": 256, "served_positions": 256,
    "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
    # at width 256 matrices drawn N(0, 0.02) give attention scores of a
    # deviation of 0.1, all but a uniform softmax that RoPE hardly moves;
    # 0.05 gives 0.64 (the published width: 1.0), and 0.0625 gives the
    # router the logits the published width has (1.0)
    "initializer_range": 0.05, "router_init_std": 0.0625,
    "source": "a toy for the CPU tests; no published model"}
TOY_ST_SERVE = dict(
    helpers.TOY_SERVE, kind="serve_open_loop_rows",
    engine={"num_slots": 4, "block_size": 16, "prefill_chunk": 32,
            "prefix_cache": False},
    # the served tokens' mean gap over the int8 reference control's on
    # the same tokens: the control reads 1 by construction; between the
    # readings at this seed: the program 0.27, its own int8 path
    # 1.54; the planted faults read 9.2 to 152
    limits={"served_over_control": 0.5})
SEED, SECONDS = 7, 3.0
# a planted fault is traced into the engine's programs, which the jit
# keeps by the model's configuration: each is a configuration of its own
FAULTS = {
    "rope-in-full-layer": lambda cfg: dataclasses.replace(
        cfg, period=tuple((True, w) for _, w in cfg.period)),
    "no-rope-in-window-layer": lambda cfg: dataclasses.replace(
        cfg, period=tuple((False, w) for _, w in cfg.period)),
    "router-after-attention": lambda cfg: dataclasses.replace(
        cfg, router_input="ffn"),
    "window-off-by-one": lambda cfg: dataclasses.replace(
        cfg, period=tuple((r, w and w - 16) for r, w in cfg.period)),
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = helpers.temp_benchmark(tmp_path_factory.mktemp("bench"))
    b = tmp / "benchmark"
    (b / "traffic" / "toy-st-serve.json").write_text(
        json.dumps(TOY_ST_SERVE))
    (b / "configs" / "toy-st.json").write_text(json.dumps(TOY_ST_CONFIG))
    m = json.loads((tmp / "BENCHMARK.json").read_text())
    m["configs"].append({
        "name": "toy-st", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/toy-st.json", "reduced": [],
        "why": "a toy of the smallthinker family"})
    m["workloads"].append({"name": "toy-st-serve", "config": "toy-st",
                           "traffic": "toy-st-serve", "chips": 1,
                           "why": "toy"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if metric["name"] in ("itl_p95_ms", "serve_tokens_per_s",
                              "dummy_count"):
            metric["workloads"].append("toy-st-serve")
    (tmp / "BENCHMARK.json").write_text(json.dumps(m))
    assert manifest.problems(m, tmp) == []
    return tmp


def test_the_toy_cell_runs_and_compares_correct(root):
    line = drive(root, "toy-st-serve", seed=SEED, seconds=SECONDS)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["itl_p95_ms"]["value"] > 0


def test_a_token_altered_is_not_correct(root):
    line = drive(root, "toy-st-serve", fault="token_altered",
                 seed=SEED, seconds=SECONDS)
    assert line["correct"] is False


def drive_cell(cell):
    line = run.drive(cell, jax.devices()[:1], PEAKS,
                     helpers.run_args(seed=SEED, seconds=SECONDS),
                     run.Phases())
    c = line["compared"]["served_over_control"]
    return line, c


def test_the_serve_control_is_not_correct(root):
    """`quant="int8_fwd"`: the projections, the experts' grouped products
    and the head through the int8 contraction."""
    cell = manifest.Cell(manifest.load(root), "toy-st-serve", root)
    cell.mix["quant"] = "int8_fwd"
    line, c = drive_cell(cell)
    assert line["correct"] is False
    assert c["value"] > c["limit"], line["compared"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_planted_in_the_program_is_not_correct(root, fault):
    """The program is given another reading of the model than the
    reference's (the window fault: a window one block short, since the
    program's window is whole blocks)."""
    cell = manifest.Cell(manifest.load(root), "toy-st-serve", root)
    fam = cell.family
    true_model = fam.program_model

    def faulty(cfg, mix):
        model = true_model(cfg, mix)
        return model.clone(cfg=FAULTS[fault](model.cfg))

    fam.program_model = faulty
    line, c = drive_cell(cell)
    assert line["correct"] is False
    assert c["value"] > c["limit"], line["compared"]


def test_the_rows_reference_reads_what_the_whole_one_reads(root):
    """The driver's reference (logits of the served positions alone)
    against `reference.ServeReference` (whole logits) on one request: the
    same gaps, the program's and the control's."""
    cell = manifest.Cell(manifest.load(root), "toy-st-serve", root)
    fam, cfg = cell.family, cell.config
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg["vocab_size"], 70).astype(np.int32)
    tokens = rng.integers(0, cfg["vocab_size"], 23).astype(np.int32)
    whole = reference.ServeReference(fam, cfg, jax.devices())
    rows = cell.driver.ServedRowsReference(fam, cfg, jax.devices())
    for ref in (whole, rows):
        ref.load(SEED)
    (g0, c0), (g1, c1) = whole.gaps(prompt, tokens), rows.gaps(prompt,
                                                               tokens)
    assert g0.shape == g1.shape == (23,) and g0.max() > 0
    np.testing.assert_allclose(g1, g0, atol=1e-5)
    np.testing.assert_allclose(c1, c0, atol=1e-5)
    # and a request that ends at the last served position
    long = rng.integers(0, cfg["vocab_size"], 250).astype(np.int32)
    g0, _ = whole.gaps(long, tokens[:7])
    g1, _ = rows.gaps(long, tokens[:7])
    np.testing.assert_allclose(g1, g0, atol=1e-5)


# -- the counts, at the published widths ----------------------------------

CELL = "smallthinker-mixedlen-steady"
NEW = ("paged_attn_roofline.mixed", "attn_window_row_share",
       "moe_experts_hit_share", "full_pool_in_use_share")
ENGINE = {"ticks": 100, "attn_full_rows": 2.0e6, "attn_window_rows": 4.0e6,
          "moe_experts_hit": 100 * 8 * 48.0, "moe_load_max": 900.0,
          "moe_load_mean": 300.0, "block_utilization": 0.2,
          "full_block_utilization": 0.2, "window_block_utilization": 0.6}


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load(), CELL)


def test_the_counts_at_the_published_widths(cell):
    fam, cfg = cell.family, cell.config
    attn = 2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560
    assert fam.attention_params(cfg) == attn == 20_971_520
    assert fam.router_params(cfg) == 163_840
    assert fam.expert_params(cfg) == 3 * 2560 * 768 == 5_898_240
    assert fam.active_layer_params(cfg) == 56_524_800
    # as cut, and at the published depth
    assert fam.total_params(cfg) == cfg["params"] == 3_966_937_600
    full = dict(cfg, num_hidden_layers=52,
                rope_layout=[0, 1, 1, 1] * 13,
                sliding_window_layout=[0, 1, 1, 1] * 13)
    assert fam.total_params(full) == 21_506_562_560
    assert fam.period(full) == fam.period(cfg) == (
        (False, 0), (True, 4096), (True, 4096), (True, 4096))
    # every published width is kept; the depth and the layouts are cut
    for key, value in (("hidden_size", 2560), ("num_attention_heads", 28),
                       ("num_key_value_heads", 4), ("head_dim", 128),
                       ("moe_num_primary_experts", 64),
                       ("moe_ffn_hidden_size", 768),
                       ("moe_num_active_primary_experts", 6),
                       ("sliding_window_size", 4096),
                       ("vocab_size", 151936), ("num_hidden_layers", 8),
                       ("published_num_hidden_layers", 52),
                       ("served_positions", 16384)):
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "rope_layout",
                              "sliding_window_layout"]
    assert cfg["assumed"] and cfg["not_built"] and cfg["deployment"]
    # a row: a key and a value of 4 heads of 128, bf16
    assert fam.row_bytes(cfg) == 2048
    assert fam.kv_bytes_per_position(cfg) == 8 * 2048
    # a query at context 5,000: two full layers 5,000 rows each, six
    # window layers 4,096 each; below the window all eight alike
    assert fam.attended_rows(cfg, 5000) == (2 * 5000, 6 * 4096)
    assert fam.attended_rows(cfg, 100) == (200, 600)
    assert fam.attended_bytes(cfg, [5000, 100]) == (
        10_000 + 24_576 + 800) * 2048
    assert fam.attended_bytes(cfg, []) == 0
    rows = 10_000 + 24_576
    assert fam.forward_flops_token(cfg, 5000, head=False) == (
        2.0 * 8 * 56_524_800 + 4.0 * 3584 * rows)
    assert (fam.forward_flops_token(cfg, 5000, head=True)
            - fam.forward_flops_token(cfg, 5000, head=False)
            == 2.0 * 2560 * 151936)
    assert fam.prefill_flops(cfg, 3) == pytest.approx(
        sum(fam.forward_flops_token(cfg, c, head=False)
            for c in (1, 2, 3)) + 2.0 * 2560 * 151936)
    # a tick's weights: attention and the float32 router of 8 layers and
    # the head, then one expert of one layer a unit of `moe_experts_hit`
    dense = 8 * (attn * 2 + 163_840 * 4) + 2560 * 151936 * 2
    assert fam.dense_weight_bytes(cfg) == dense
    assert fam.expert_bytes(cfg) == 11_796_480
    assert fam.routed_experts(cfg) == 512
    assert fam.decode_weight_bytes(cfg) == dense + 512 * 11_796_480
    assert abs(512 * 11_796_480 / 6.04e9 - 1) < 0.001    # the issue's
    assert fam.decode_tick_bytes(cfg, [5000, 100], 300.0) == (
        dense + 300 * 11_796_480 + fam.attended_bytes(cfg, [5000, 100]))


def ctx_for(cell, engine):
    rec = types.SimpleNamespace(prompt_len=5000,
                                token_times=[0.5 + 0.01 * j
                                             for j in range(100)])
    runs = [types.SimpleNamespace(dur=12e6) for _ in range(40)]
    trace = types.SimpleNamespace(
        program_runs=lambda name: runs,
        scope_time=lambda scope, rs: 0.004 * len(rs))
    return types.SimpleNamespace(
        config=cell.config, mix=cell.mix, family=cell.family,
        peaks=peaks.lookup("TPU v5 lite"), trace=trace,
        trace_span=(0.0, 2.0), records=[rec],
        counters={"engine": engine})


def test_the_cell_is_the_issues(cell):
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    for joined in ("decode_tick_roofline.sparse", "moe_load_max_over_mean",
                   "window_pool_in_use_share", "step_mfu.serve",
                   "decode_tick_ms", "prefill_chunk_ms", "ttft_p95_ms"):
        assert joined in names
    # `served.decode_work` counts every layer at the whole context, which
    # would read a window layer over 100%; the pool shares below move an
    # end-to-end metric the cell does not report; all experts are held
    for other in ("decode_tick_roofline", "paged_attn_roofline",
                  "kv_pool_in_use_share", "batch_occupancy",
                  "moe_held_assignment_share"):
        assert other not in names
    assert {m["name"] for m in cell.end_to_end} == {"itl_p95_ms", "setup_s"}
    assert cell.chips == 1 and len(cell.entry["why"]) <= 200
    mix = cell.mix
    assert mix["rate_rps"] == pytest.approx(0.8 * mix["knee_rps"])
    assert (mix["ramp_s"], mix["trace_s"], mix["drain"]) == (20, 4, True)
    assert mix["compare_requests"] == 4
    assert mix["first_token_timeout_s"] == 60
    assert mix["engine"] == {"num_slots": 32, "block_size": 16,
                             "prefill_chunk": 512, "prefix_cache": False}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                    "sigma": 0.9, "min": 256, "max": 12288}
    assert mix["answer_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.5, "min": 128, "max": 1024}
    assert list(mix["limits"]) == ["served_over_control"]


@pytest.mark.parametrize("name", NEW + ("decode_tick_roofline.sparse",))
def test_a_reader_reads_or_returns_none(cell, name):
    got = cell.reader(name)(ctx_for(cell, ENGINE))
    assert got is not None and 0 < got <= 100
    # nothing to read: no counters (an older program), or no traced tick
    empty = ctx_for(cell, {"ticks": 100})
    empty.trace = types.SimpleNamespace(program_runs=lambda name: [],
                                        scope_time=lambda s, r: 0.0)
    assert cell.reader(name)(empty) is None


def test_the_readers_give_what_the_counters_say(cell):
    ctx = ctx_for(cell, ENGINE)
    assert cell.reader("attn_window_row_share")(ctx) == pytest.approx(
        200 / 3)
    assert cell.reader("moe_experts_hit_share")(ctx) == pytest.approx(75.0)
    assert cell.reader("full_pool_in_use_share")(ctx) == 20.0
    assert cell.reader("window_pool_in_use_share")(ctx) == 60.0
    assert cell.reader("moe_load_max_over_mean")(ctx) == 3.0
    # 99 ticks of one stream at contexts 5,001..5,099; 40 traced runs of
    # 12 ms, 4 ms of each under the attention scope
    fam, cfg = cell.family, cell.config
    contexts = list(range(5001, 5100))
    assert cell.reader("paged_attn_roofline.mixed")(ctx) == pytest.approx(
        100 * fam.attended_bytes(cfg, contexts) / 819e9 / 0.16, rel=1e-6)
    # the accepted sparse reader, as it is: `moe_experts_hit` over the
    # ticks is experts a tick over the layers, `expert_bytes` its unit
    nbytes = (fam.decode_tick_bytes(cfg, contexts, 0.0)
              + 39 * fam.dense_weight_bytes(cfg)
              + 40 * 8 * 48.0 * fam.expert_bytes(cfg))
    assert cell.reader("decode_tick_roofline.sparse")(
        ctx) == pytest.approx(100 * nbytes / 819e9 / 0.48, rel=1e-3)
