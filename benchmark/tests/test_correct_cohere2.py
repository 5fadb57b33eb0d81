"""`correct` for the `cohere2_moe` family at a toy size on the CPU, as
`test_correct_jamba.py` has it for the family there: the toy cell runs
through the harness (the `serve_open_loop_rows` driver) and compares
correct; an altered token, the control (the engine with the program's
own int8 path) and each fault planted in what this family brought to the
program come out not correct: the block made sequential, the shared
experts summed where they are averaged, RoPE in the full layers, the
loader's RoPE permutation left out. The family's counts are pinned at the
published widths, the cell's mix is pinned, and the new reader reads or
returns `None`.

The cell is added to a temporary copy of the benchmark as new files and
entries (`helpers.temp_benchmark`, then a configuration, one mix and a
cell more); the family file and the driver are the repository's own.
"""

import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, peaks, reference, run

import helpers
from test_correct import PEAKS, drive

# Command A+'s shape (parallel blocks, three RoPE window layers then a
# NoPE full layer, grouped heads, half of the experts held beside
# averaged shared experts, a tied head), wide enough that the program's
# int8 path reads apart from its bf16 path; prompts cross the window
TOY_CA_CONFIG = {
    "model_type": "cohere2_moe", "hidden_size": 256, "head_dim": 32,
    "num_attention_heads": 8, "num_key_value_heads": 2,
    "num_hidden_layers": 8,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "sliding_window": 32, "num_experts": 8, "published_num_experts": 16,
    "experts_held": [0, 8], "num_experts_per_tok": 4,
    "num_shared_experts": 2, "intermediate_size": 128,
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "shared_expert_combination_strategy": "average",
    "use_parallel_block": True, "tie_word_embeddings": True,
    "position_embedding_type": "rope_gptj", "rotary_pct": 1,
    "logit_scale": 1, "layer_norm_eps": 1e-5, "rope_theta": 50000,
    "vocab_size": 1024, "max_position_embeddings": 256,
    "served_positions": 256, "param_dtype": "bfloat16",
    "compute_dtype": "bfloat16",
    # at width 256 matrices drawn N(0, 0.02) give attention scores of a
    # deviation of 0.1; 0.05 gives 0.64, and 0.0625 gives the router the
    # logits the published width has (1.3)
    "initializer_range": 0.05, "router_init_std": 0.0625,
    "source": "a toy for the CPU tests; no published model"}
TOY_CA_SERVE = dict(
    helpers.TOY_SERVE, kind="serve_open_loop_rows",
    engine={"num_slots": 4, "block_size": 16, "prefill_chunk": 32,
            "prefix_cache": False},
    prompt_tokens={"dist": "lognormal", "median": 48, "sigma": 0.4,
                   "min": 16, "max": 128},
    # the served tokens' mean gap over the int8 reference control's on
    # the same tokens: the control reads 1 by construction
    limits={"served_over_control": 0.6})
SEED, SECONDS = 7, 3.0


def _sequential(fam):
    """The block made sequential: the program's other block form, its
    second norm given the first's gains."""
    model, tree = fam.program_model, fam.to_program_tree

    def program_model(cfg, mix):
        m = model(cfg, mix)
        return m.clone(cfg=dataclasses.replace(m.cfg,
                                               block_form="sequential"))

    def to_program_tree(w, cfg, mix):
        out = tree(w, cfg, mix)
        for layer in out["params"]["h"]["block"].values():
            layer["ln2"] = layer["ln1"]
        return out

    return {"program_model": program_model,
            "to_program_tree": to_program_tree}


def _rope_in_full_layer(fam):
    model = fam.program_model

    def program_model(cfg, mix):
        m = model(cfg, mix)
        return m.clone(cfg=dataclasses.replace(
            m.cfg, period=tuple((True, w) for _, w in m.cfg.period)))

    return {"program_model": program_model}


#: faults planted in the program or its loader, each a reading of the
#: model that the config rules out
FAULTS = {
    "sequential-block": _sequential,
    "shared-summed": lambda fam: {"_shared_divisor": lambda n: 1},
    "rope-in-full-layer": _rope_in_full_layer,
    "rope-not-permuted": lambda fam: {
        "rope_permutation": lambda d: np.arange(d)},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = helpers.temp_benchmark(tmp_path_factory.mktemp("bench"))
    b = tmp / "benchmark"
    (b / "traffic" / "toy-ca-serve.json").write_text(
        json.dumps(TOY_CA_SERVE))
    (b / "configs" / "toy-ca.json").write_text(json.dumps(TOY_CA_CONFIG))
    m = json.loads((tmp / "BENCHMARK.json").read_text())
    m["configs"].append({
        "name": "toy-ca", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/toy-ca.json", "reduced": [],
        "why": "a toy of the cohere2_moe family"})
    m["workloads"].append({"name": "toy-ca-serve", "config": "toy-ca",
                           "traffic": "toy-ca-serve", "chips": 1,
                           "why": "toy"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if metric["name"] in ("itl_p95_ms", "serve_tokens_per_s",
                              "dummy_count"):
            metric["workloads"].append("toy-ca-serve")
    (tmp / "BENCHMARK.json").write_text(json.dumps(m))
    assert manifest.problems(m, tmp) == []
    return tmp


def drive_cell(cell):
    line = run.drive(cell, jax.devices()[:1], PEAKS,
                     helpers.run_args(seed=SEED, seconds=SECONDS),
                     run.Phases())
    return line, line["compared"]["served_over_control"]


def test_the_toy_cell_runs_and_compares_correct(root):
    line = drive(root, "toy-ca-serve", seed=SEED, seconds=SECONDS)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["itl_p95_ms"]["value"] > 0


def test_a_token_altered_is_not_correct(root):
    line = drive(root, "toy-ca-serve", fault="token_altered",
                 seed=SEED, seconds=SECONDS)
    assert line["correct"] is False


def test_the_serve_control_is_not_correct(root):
    """`quant="int8_fwd"`: the projections, the experts' grouped products,
    the shared experts and the tied head through the int8 contraction."""
    cell = manifest.Cell(manifest.load(root), "toy-ca-serve", root)
    cell.mix["quant"] = "int8_fwd"
    line, c = drive_cell(cell)
    assert line["correct"] is False
    assert c["value"] > c["limit"], line["compared"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_planted_in_the_program_is_not_correct(root, fault,
                                                       monkeypatch):
    cell = manifest.Cell(manifest.load(root), "toy-ca-serve", root)
    fam = cell.family
    for name, value in FAULTS[fault](fam).items():
        monkeypatch.setattr(fam, name, value)
    true_model = fam.program_model
    extra = 16 * (1 + list(FAULTS).index(fault))

    def faulty(cfg, mix):
        # a longer table: a configuration of its own, which the jit
        # keeps programs by
        model = true_model(cfg, mix)
        return model.clone(cfg=dataclasses.replace(
            model.cfg, max_seq_len=model.cfg.max_seq_len + extra))

    monkeypatch.setattr(fam, "program_model", faulty)
    line, c = drive_cell(cell)
    assert line["correct"] is False
    assert c["value"] > c["limit"], line["compared"]


def test_the_rows_reference_reads_what_the_whole_one_reads(root):
    """The driver's reference (logits of the served positions alone)
    against `reference.ServeReference` (whole logits) on one request: the
    same gaps, the program's and the control's."""
    cell = manifest.Cell(manifest.load(root), "toy-ca-serve", root)
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


def test_the_reference_faults_move_the_logits():
    fam = manifest.load_family(manifest.BENCH_DIR, "cohere2_moe")
    cfg = dict(TOY_CA_CONFIG, compute_dtype="float32")
    w = fam.make_weights(cfg, reference.seed_u32(3))
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (1, 64)), jnp.int32)
    ref = fam.forward(cfg, w, tokens)
    for fault in fam.FAULTS:
        got = fam.forward(cfg, w, tokens, fault=fault)
        assert float(jnp.abs(got - ref).max() / jnp.abs(ref).max()) > 1e-3


# -- the counts, at the published widths ----------------------------------

CELL = "cmdaplus-rag-steady"
NEW = ("expert_banks_roofline",)
ENGINE = {"ticks": 100, "prefill_chunks": 20, "slot_occupancy": 0.75,
          "moe_experts_hit": 100 * 4 * 12.0,
          "moe_assignments_held": 100 * 4 * 24.0,
          "chunk_moe_experts_hit": 20 * 4 * 16.0,
          "chunk_moe_assignments_held": 20 * 4 * 512.0,
          "moe_assignments_total": 100 * 4 * 24 * 8.0}


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load(), CELL)


def test_the_counts_at_the_published_widths(cell):
    fam, cfg = cell.family, cell.config
    for key, value in (("hidden_size", 4096), ("num_attention_heads", 128),
                       ("num_key_value_heads", 8), ("head_dim", 128),
                       ("intermediate_size", 4096), ("num_experts", 16),
                       ("published_num_experts", 128),
                       ("experts_held", [0, 16]),
                       ("num_experts_per_tok", 8),
                       ("num_shared_experts", 4), ("sliding_window", 4096),
                       ("num_hidden_layers", 4), ("vocab_size", 32768),
                       ("published_vocab_size", 262144),
                       ("published_num_hidden_layers", 32),
                       ("served_positions", 33792),
                       ("model_type", "cohere2_moe")):
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_experts", "vocab_size"]
    assert cfg["assumed"] and cfg["not_built"] and cfg["deployment"]
    assert fam.period(cfg) == ((True, 4096),) * 3 + ((False, 0),)
    # a layer outside the routed experts: q 67.1 M, k and v 4.2 M each, o
    # 67.1 M, four shared experts 201.3 M, the router 0.5 M: 344.4 M
    assert fam.attention_params(cfg) == 142_606_336
    assert fam.shared_params(cfg) == 4 * 3 * 4096 * 4096 == 201_326_592
    assert fam.router_params(cfg) == 4096 * 128
    assert fam.nonrouted_layer_params(cfg) == 344_457_216
    assert round(fam.nonrouted_layer_params(cfg) / 1e6, 1) == 344.5
    assert fam.expert_params(cfg) == 50_331_648
    # this chip: 4 x (344.46 M + 4,096 + 16 x 50.33 M) + 134.2 M + 4,096
    assert fam.total_params(cfg) == cfg["params"] == 4_733_292_544
    assert round(fam.total_params(cfg) * 2 / 1e9, 2) == 9.47
    assert fam.routed_experts(cfg) == 64
    assert fam.expert_bytes(cfg) == 2 * 50_331_648
    assert fam.kv_bytes_per_position(cfg) == 4 * 4096
    assert fam.dense_weight_bytes(cfg) == (
        4 * ((142_606_336 + 201_326_592) * 2 + 524_288 * 4)
        + 4096 * 32768 * 2)
    # a tick of 24 streams at 8,192: the full layer reads 8,192 rows, the
    # window layers 4,096 each, of 4,096 B
    tick = fam.decode_tick_bytes(cfg, [8192] * 24, 4 * 12.0)
    assert tick == (fam.dense_weight_bytes(cfg) + 48 * 100_663_296
                    + 24 * (8192 + 3 * 4096) * 4096)
    # FLOPs: one held expert a token a layer on average (8 x 16 / 128)
    mats = 4 * (344_457_216 + 50_331_648)
    assert fam.forward_flops_token(cfg, 5000, head=False) == pytest.approx(
        2.0 * mats + 4.0 * 128 * 128 * (5000 + 3 * 4096))
    assert (fam.forward_flops_token(cfg, 5000, head=True)
            - fam.forward_flops_token(cfg, 5000, head=False)
            == 2.0 * 4096 * 32768)
    assert fam.prefill_flops(cfg, 3) == pytest.approx(
        sum(fam.forward_flops_token(cfg, c, head=False)
            for c in (1, 2, 3)) + 2.0 * 4096 * 32768)


def test_the_catalog_numbers_are_kept():
    """Every number of the published config at the top level is the
    file's, but for the keys `reduced` names."""
    cfg = manifest.Cell(manifest.load(), CELL).config
    published = {"first_k_dense_replace": 0, "head_dim": 128,
                 "hidden_size": 4096, "intermediate_size": 4096,
                 "layer_norm_eps": 1e-5, "layer_switch": 4,
                 "logit_scale": 1, "max_position_embeddings": 200000,
                 "num_attention_heads": 128, "num_experts_per_tok": 8,
                 "num_key_value_heads": 8, "num_shared_experts": 4,
                 "prefix_dense_intermediate_size": 16384,
                 "prefix_dense_sliding_window_pattern": 1,
                 "rope_theta": 50000, "rotary_pct": 1,
                 "sliding_window": 4096}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["rope_parameters"] == {"rope_theta": 50000,
                                      "rope_type": "default"}


def test_the_cell_and_its_mix_are_pinned(cell):
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    st = manifest.Cell(manifest.load(), "smallthinker-mixedlen-steady")
    assert {m["name"] for m in st.per_layer} | {
        "moe_held_assignment_share"} == names
    assert {m["name"] for m in cell.end_to_end} == {"itl_p95_ms", "setup_s"}
    assert cell.chips == 1 and len(cell.entry["why"]) <= 200
    mix = cell.mix
    assert mix["kind"] == "serve_open_loop_rows"
    assert mix["rate_rps"] == pytest.approx(0.8 * mix["knee_rps"])
    assert (mix["ramp_s"], mix["trace_s"], mix["drain"]) == (20, 4, True)
    assert mix["compare_requests"] == 4 and mix["greedy"]
    assert mix["arrivals"] == {"dist": "exponential"}
    assert mix["engine"] == {"num_slots": 32, "block_size": 16,
                             "prefill_chunk": 512, "prefix_cache": False,
                             "num_blocks": 32769}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                    "sigma": 0.6, "min": 1024,
                                    "max": 32768}
    assert mix["answer_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.5, "min": 64, "max": 1024}
    assert (mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"]
            == cell.config["served_positions"])
    assert mix["limits"] == {"served_over_control": 0.6}


def ctx_for(cell, engine, banks_s=1.0, streams=12):
    """40 traced ticks and 10 traced chunks; `streams` streams delivering
    a token each traced tick (the window's ticks held 24 a tick)."""
    records = [types.SimpleNamespace(prompt_len=500, token_times=[
        0.01 + 0.04 * j for j in range(41)]) for _ in range(streams)]
    ticks = [types.SimpleNamespace(dur=15e6) for _ in range(40)]
    chunks = [types.SimpleNamespace(dur=30e6) for _ in range(10)]
    trace = types.SimpleNamespace(
        program_runs=lambda name: ticks if "tick" in name else chunks,
        scope_time=lambda scope, rs=None: banks_s
        if scope == "expert_banks" else 0.0)
    return types.SimpleNamespace(
        config=cell.config, mix=cell.mix, family=cell.family,
        peaks=peaks.lookup("TPU v5 lite"), trace=trace,
        trace_span=(0.0, 2.0), records=records,
        counters={"engine": engine})


@pytest.mark.parametrize("name", ["cmdaplus-rag-steady",
                                  "smallthinker-mixedlen-steady"])
def test_the_new_reader_reads_or_returns_none(name):
    cell = manifest.Cell(manifest.load(), name)
    reader = cell.reader("expert_banks_roofline")
    got = reader(ctx_for(cell, ENGINE))
    assert got is not None and 0 < got <= 100
    # the parent's program counts no chunk's experts; no kernel traced
    older = {k: v for k, v in ENGINE.items() if not k.startswith("chunk_")}
    assert reader(ctx_for(cell, older)) is None
    assert reader(ctx_for(cell, ENGINE, banks_s=0.0)) is None


@pytest.mark.parametrize("streams,fill", [(12, 0.5), (36, 1.5)])
def test_the_reader_gives_what_the_counts_say(cell, streams, fill):
    """The window's ticks read 48 experts and 96 rows each at 24 live
    tokens, its chunks 64 experts and 2,048 rows each. The traced ticks
    delivered 12 (36) tokens a tick: half (one and a half times) the
    rows, half the experts (the window's, never more)."""
    fam, cfg = cell.family, cell.config
    hit = 40 * 48 * min(1.0, fill) + 10 * 64
    rows = 40 * 96 * fill + 10 * 2048
    nbytes = hit * fam.expert_bytes(cfg) + rows * (
        2 * (2 * 4096 + 4096) + 4 * (3 * 4096))
    got = cell.reader("expert_banks_roofline")(
        ctx_for(cell, ENGINE, streams=streams))
    assert got == pytest.approx(100 * nbytes / 819e9 / 1.0)
