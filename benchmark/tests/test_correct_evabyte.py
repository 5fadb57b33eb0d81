"""`correct` for the `evabyte` family at a toy size on the CPU, as
`test_correct_dots3.py` has it for the family there: the toy cell runs
through the harness and compares correct; the control (the engine with
the program's own int8 path) comes out not correct, and so do two faults
planted in what this family brought to the program: the summaries left
out of the softmax, and the device taking a window as retired one window
late (it reads the rows the host has already handed back). The family's
counts are pinned against a hand count, and its readers read or return
`None`.

The cells are added to a temporary copy of the benchmark as new files and
entries (`helpers.temp_benchmark`, then configurations, one mix and
cells more); the family file is the repository's own.
"""

import json
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import manifest, peaks, run

import helpers
from test_correct import PEAKS, drive

# EvaByte's shape (the Llama dialect, a tumbling window, a summary a
# chunk, a unit-offset norm, 320 bytes), wide enough that the program's
# int8 path reads apart from its bf16 path in the served tokens; prompts
# and answers cross one to three windows
TOY_EVA_CONFIG = {
    "model_type": "evabyte", "attention_class": "eva", "hidden_act": "silu",
    "hidden_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 6,
    "intermediate_size": 512, "vocab_size": 320, "window_size": 32,
    "chunk_size": 4, "rope_theta": 100000, "rms_norm_eps": 1e-5,
    "norm_add_unit_offset": True, "fp32_skip_add": True,
    "fp32_logits": True, "mixedp_attn": True,
    "max_position_embeddings": 256, "served_positions": 256,
    "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
    "initializer_range": 0.02, "summary_init_std": 0.5,
    "source": "a toy for the CPU tests; no published model"}
TOY_EVA_SERVE = dict(
    helpers.TOY_SERVE,
    engine={"num_slots": 4, "block_size": 4, "prefill_chunk": 32,
            "prefix_cache": False},
    limits={"served_mean_gap": 1.0e-4})
SEED, SECONDS = 7, 3.0
# a planted fault is traced into the engine's programs, which the jit
# keeps by the model's configuration: each runs under a context length of
# its own, so that no program traced without the fault is found again
CELLS = {"toy-eva-serve": 256, "toy-eva-no-summaries": 288,
         "toy-eva-late-window": 320}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = helpers.temp_benchmark(tmp_path_factory.mktemp("bench"))
    b = tmp / "benchmark"
    (b / "traffic" / "toy-eva-serve.json").write_text(
        json.dumps(TOY_EVA_SERVE))
    m = json.loads((tmp / "BENCHMARK.json").read_text())
    for name, served in CELLS.items():
        (b / "configs" / f"{name}.json").write_text(json.dumps(
            dict(TOY_EVA_CONFIG, served_positions=served)))
        m["configs"].append({
            "name": name, "source": "none: a toy for the CPU tests",
            "file": f"benchmark/configs/{name}.json", "reduced": [],
            "why": "a toy of the evabyte family"})
        m["workloads"].append({"name": name, "config": name,
                               "traffic": "toy-eva-serve", "chips": 1,
                               "why": "toy"})
        for metric in m["end_to_end"] + m["per_layer"]:
            if metric["name"] in ("itl_p95_ms", "serve_tokens_per_s",
                                  "dummy_count"):
                metric["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(m))
    assert manifest.problems(m, tmp) == []
    return tmp


def test_the_toy_cell_runs_and_compares_correct(root):
    line = drive(root, "toy-eva-serve", seed=SEED, seconds=SECONDS)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["itl_p95_ms"]["value"] > 0


def test_a_token_altered_is_not_correct(root):
    line = drive(root, "toy-eva-serve", fault="token_altered",
                 seed=SEED, seconds=SECONDS)
    assert line["correct"] is False


def test_the_serve_control_is_not_correct(root):
    """`quant="int8_fwd"`: the fused q/k/v and output projections, the
    feed-forward and the head through the int8 contraction."""
    cell = manifest.Cell(manifest.load(root), "toy-eva-serve", root)
    cell.mix["quant"] = "int8_fwd"
    line = run.drive(cell, jax.devices()[:1], PEAKS,
                     helpers.run_args(seed=SEED, seconds=SECONDS),
                     run.Phases())
    assert line["correct"] is False
    c = line["compared"]["served_mean_gap"]
    assert c["value"] > c["limit"], line["compared"]


def not_correct_under(root, name):
    line = drive(root, name, seed=SEED, seconds=SECONDS)
    assert line["correct"] is False
    c = line["compared"]["served_mean_gap"]
    assert c["value"] > c["limit"], line["compared"]


def test_summaries_left_out_of_the_softmax_are_not_correct(root,
                                                           monkeypatch):
    from pytorchdistributed_tpu.models import eva

    monkeypatch.setattr(eva, "summaries_seen", lambda w, per: 0 * w)
    not_correct_under(root, "toy-eva-no-summaries")


def test_a_window_retired_one_window_late_is_not_correct(root, monkeypatch):
    """The device's window lags the host's by one: past the first
    boundary a query reads, as its window, blocks the engine has already
    handed back, and sees one window's summaries too few."""
    from pytorchdistributed_tpu.models import eva

    monkeypatch.setattr(
        eva, "window_of", lambda pos, win: jnp.maximum(pos // win - 1, 0))
    not_correct_under(root, "toy-eva-late-window")


def test_the_reference_without_summaries_differs(root):
    """The fault's twin in the reference: leaving the summaries out moves
    the logits past the first window, and only there."""
    cell = manifest.Cell(manifest.load(root), "toy-eva-serve", root)
    fam, cfg = cell.family, cell.config
    w = fam.make_weights(cfg, jnp.uint32(3))
    toks = jax.random.randint(jax.random.key(1), (1, 80), 0,
                              cfg["vocab_size"])
    full = fam.forward(cfg, w, toks)
    cut = fam.forward(cfg, w, toks, summaries=False)
    win = cfg["window_size"]
    assert float(jnp.abs(full - cut)[0, :win].max()) == 0.0
    assert float(jnp.abs(full - cut)[0, win:].max()) > 1e-3


# -- the counts, against a hand count at the published sizes -------------

CELL = "evabyte-longgen-saturated"
NEW = ("decode_tick_roofline.eva", "eva_summary_row_share",
       "summary_pool_in_use_share")
ENGINE = {"ticks": 100, "eva_window_rows": 6.0e6, "eva_summary_rows": 3.0e6,
          "eva_summaries_written": 800.0, "block_utilization": 0.31,
          "summary_block_utilization": 0.31,
          "window_block_utilization": 0.52}


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load(), CELL)


def test_the_counts_against_a_hand_count(cell):
    fam, cfg = cell.family, cell.config
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008          # 202,375,168
    assert fam.layer_matmul_params(cfg) == layer == 202_375_168
    assert fam.matmul_params(cfg) == 8 * layer + 4096 * 320
    assert fam.decode_weight_bytes(cfg) == 2 * (8 * layer + 4096 * 320)
    assert abs(fam.decode_weight_bytes(cfg) / 3.24e9 - 1) < 0.002
    # the embedding, 17 gains, phi and mu of 8 layers beside the matrices
    assert fam.total_params(cfg) == (8 * layer + 2 * 320 * 4096
                                     + 17 * 4096 + 2 * 8 * 4096)
    assert cfg["params"] == fam.total_params(cfg)
    # a row: a key and a value of 32 heads of 128, bf16; a position over
    # the 8 layers while its window lasts
    assert fam.row_bytes(cfg) == 16_384
    assert fam.kv_bytes_per_position(cfg) == 8 * 16_384
    # the query at position 5,000 (context 5,001, itself included):
    # window 2, 905 exact rows, 256 summaries
    assert fam.attended_rows(cfg, 5001) == (5000 % 2048 + 1, 2 * 128)
    assert fam.attended_rows(cfg, 5001) == (905, 256)
    assert fam.attended_rows(cfg, 2048) == (2048, 0)   # window 0's last
    assert fam.attended_rows(cfg, 2049) == (1, 128)    # window 1's first
    rows = 905 + 256
    assert fam.forward_flops_token(cfg, 5001, head=False) == (
        2.0 * 8 * layer + 4.0 * 8 * 4096 * rows + 6.0 * 8 * 4096)
    assert (fam.forward_flops_token(cfg, 5001, head=True)
            - fam.forward_flops_token(cfg, 5001, head=False)
            == 2.0 * 4096 * 320)
    # a prompt is its tokens, the head once
    assert fam.prefill_flops(cfg, 3) == pytest.approx(
        sum(fam.forward_flops_token(cfg, c, head=False)
            for c in (1, 2, 3)) + 2.0 * 4096 * 320)
    # a tick: the weights once; the stream above reads 1,161 rows a
    # layer and fills no chunk (5,001 is no multiple of 16); one at
    # context 5,008 writes one row more
    base = fam.decode_tick_bytes(cfg, [])
    assert base == fam.decode_weight_bytes(cfg)
    assert fam.decode_tick_bytes(cfg, [5001]) - base == rows * 8 * 16_384
    assert (fam.decode_tick_bytes(cfg, [5008]) - base
            == (912 + 256 + 1) * 8 * 16_384)
    # the issue's reckoning of a tick at this traffic: 16 streams with
    # ~1,000 window rows and ~500 summary rows each, ~3.2 GB of cache
    tick = fam.decode_tick_bytes(cfg, [5 * 2048 - 1040] * 16) - base
    assert abs(tick / 3.2e9 - 1) < 0.05


def ctx_for(cell, engine):
    rec = types.SimpleNamespace(prompt_len=8000,
                                token_times=[0.5 + 0.01 * j
                                             for j in range(100)])
    runs = [types.SimpleNamespace(dur=40e6) for _ in range(40)]
    trace = types.SimpleNamespace(program_runs=lambda name: runs)
    return types.SimpleNamespace(
        config=cell.config, mix=cell.mix, family=cell.family,
        peaks=peaks.lookup("TPU v5 lite"), trace=trace,
        trace_span=(0.0, 2.0), records=[rec],
        counters={"engine": engine})


def test_the_cell_lists_its_readers_and_not_the_dense_ones(cell):
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names and "window_pool_in_use_share" in names
    # the chunk is the larger half of the cell's gap between tokens:
    # `trace_s` is long enough that every traced run holds some
    assert "prefill_chunk_ms" in names and cell.mix["trace_s"] >= 8
    for other in ("decode_tick_roofline", "decode_tick_roofline.sparse",
                  "paged_attn_roofline", "kv_pool_in_use_share",
                  "batch_occupancy"):
        assert other not in names
    # judged on the gap between tokens: the tokens a second of 25 long
    # requests a window spread by over 6% over six seeds, twice what half
    # the bound allows (PERF.md, section 4), and `batch_occupancy` moves
    # an end-to-end metric the cell does not report
    assert {m["name"] for m in cell.end_to_end} == {"itl_p95_ms", "setup_s"}
    mix = cell.mix
    assert mix["rate_rps"] == pytest.approx(1.25 * mix["knee_rps"])
    assert mix["ramp_s"] == 20 and mix["drain"] is False
    assert mix["engine"] == {"num_slots": 16, "block_size": 16,
                             "prefill_chunk": 1024, "prefix_cache": False}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_or_returns_none(cell, name):
    got = cell.reader(name)(ctx_for(cell, ENGINE))
    assert got is not None and 0 < got <= 100
    # nothing to read: no counters (an older program), or no traced tick
    empty = ctx_for(cell, {"ticks": 100})
    empty.trace = types.SimpleNamespace(program_runs=lambda name: [])
    assert cell.reader(name)(empty) is None


def test_the_readers_give_what_the_counters_say(cell):
    ctx = ctx_for(cell, ENGINE)
    assert cell.reader("eva_summary_row_share")(ctx) == pytest.approx(
        100 / 3)
    assert cell.reader("summary_pool_in_use_share")(ctx) == 31.0
    assert cell.reader("window_pool_in_use_share")(ctx) == 52.0
    # the roofline: 99 ticks of one stream at contexts 8,001..8,099, 40
    # traced runs of 40 ms
    fam, cfg = cell.family, cell.config
    contexts = list(range(8001, 8100))
    nbytes = (fam.decode_tick_bytes(cfg, contexts)
              + 39 * fam.decode_weight_bytes(cfg))
    assert cell.reader("decode_tick_roofline.eva")(ctx) == pytest.approx(
        100 * nbytes / 819e9 / 1.6, rel=1e-3)
