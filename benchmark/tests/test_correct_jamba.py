"""`correct` for the `jamba` family at a toy size on the CPU, as
`test_correct_smallthinker.py` has it for the family there: the toy cell
runs through the harness (the `serve_open_loop_rows` driver) and compares
correct; an altered token, the control (the engine with the program's
own int8 path) and each fault planted in what this family brought to the
program come out not correct: the state kept in bfloat16, the state or
the convolution's window not carried from one chunk to the next, the
step, B and C not normed. The family's counts are pinned at the published
widths, the cell is the issue's, and its readers read or return `None`.

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

# Jamba's shape (mamba mixers with dt/B/C norms beside one NoPE attention
# layer a period over one key/value head, a dense SwiGLU, a tied head),
# wide enough that the program's int8 path reads apart from its bf16
# path; prompts of one to four chunks, so that a state and a window cross
# chunk edges
TOY_JB_CONFIG = {
    "model_type": "jamba", "hidden_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 1, "num_hidden_layers": 8,
    "attn_layer_period": 4, "attn_layer_offset": 2, "mamba_expand": 2,
    "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_dt_rank": 16,
    "mamba_conv_bias": True, "mamba_proj_bias": False, "num_experts": 1,
    "intermediate_size": 512, "vocab_size": 4096, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "max_position_embeddings": 256,
    "served_positions": 256, "param_dtype": "bfloat16",
    "compute_dtype": "bfloat16", "initializer_range": 0.05,
    "source": "a toy for the CPU tests; no published model"}
TOY_JB_SERVE = dict(
    helpers.TOY_SERVE, kind="serve_open_loop_rows",
    engine={"num_slots": 4, "block_size": 16, "prefill_chunk": 32,
            "prefix_cache": False},
    prompt_tokens={"dist": "lognormal", "median": 64, "sigma": 0.4,
                   "min": 24, "max": 128},
    # the served tokens' mean gap over the int8 reference control's on
    # the same tokens: the control reads 1 by construction; at this seed
    # the program reads 0.039-0.060 (both toy cells), its own int8 path
    # 1.03, the planted faults 0.68 (a bf16 state, on the long cell), 1.09
    # (the window not carried), 7.8 (the state not carried), 41 (no norms)
    limits={"served_over_control": 0.4})
# a bfloat16 state drifts as a stream grows (a decay of 1 - 1e-3 rounds to
# 1): at a reference's 64 positions it moves the logits a tenth of what
# the bf16 arithmetic does, at 1,000 and more four times it (PERF.md), so
# that fault is read on prompts of 1,024 to 2,048
TOY_JB_LONG = dict(
    TOY_JB_SERVE, rate_rps=2.0,
    engine=dict(TOY_JB_SERVE["engine"], prefill_chunk=256),
    prompt_tokens={"dist": "lognormal", "median": 1536, "sigma": 0.3,
                   "min": 1024, "max": 2048})
SEED, SECONDS = 7, 3.0


def forget(leaf):
    """A program that does not carry `leaf` (a state leaf) from one chunk
    of a prompt to the next: the engine's chunk program handed the
    slot's row zeroed wherever the chunk does not start the stream."""
    from pytorchdistributed_tpu.serving import engine as engine_mod

    real = engine_mod.paged_prefill_chunk

    def chunk(model, weights, cache, chunk, start, *args, **kw):
        slot = args[7]
        if int(start) > 0:
            cache = jax.tree_util.tree_map_with_path(
                lambda p, x: (x.at[:, slot].set(0)
                              if getattr(p[-1], "key", None) == leaf
                              else x), cache)
        return real(model, weights, cache, chunk, start, *args, **kw)

    return lambda mp: mp.setattr(engine_mod, "paged_prefill_chunk", chunk)


def traced(module, name, value):
    """A fault traced into the programs: `module.name` set to `value`."""
    def plant(mp):
        import importlib

        mp.setattr(importlib.import_module(module), name, value)
    return plant


# a planted fault: traced into the programs (each then under a
# configuration of its own, which the jit keeps programs by), or the
# engine's chunk program handed a forgotten row
FAULTS = {
    "bf16_state": traced("pytorchdistributed_tpu.ops.ssm_scan",
                         "STATE_DTYPE", jnp.bfloat16),
    "no_dt_bc_norm": traced("pytorchdistributed_tpu.models.ssm", "_rms",
                            lambda x, g, eps: x),
    "state_not_carried": forget("cached_ssm_state"),
    "conv_not_carried": forget("cached_conv_state"),
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = helpers.temp_benchmark(tmp_path_factory.mktemp("bench"))
    b = tmp / "benchmark"
    (b / "traffic" / "toy-jb-serve.json").write_text(
        json.dumps(TOY_JB_SERVE))
    (b / "traffic" / "toy-jb-long.json").write_text(json.dumps(TOY_JB_LONG))
    (b / "configs" / "toy-jb.json").write_text(json.dumps(TOY_JB_CONFIG))
    (b / "configs" / "toy-jb-long.json").write_text(json.dumps(dict(
        TOY_JB_CONFIG, served_positions=2112)))
    m = json.loads((tmp / "BENCHMARK.json").read_text())
    for name in ("toy-jb", "toy-jb-long"):
        m["configs"].append({
            "name": name, "source": "none: a toy for the CPU tests",
            "file": f"benchmark/configs/{name}.json", "reduced": [],
            "why": "a toy of the jamba family"})
    m["workloads"] += [
        {"name": "toy-jb-serve", "config": "toy-jb",
         "traffic": "toy-jb-serve", "chips": 1, "why": "toy"},
        {"name": "toy-jb-long", "config": "toy-jb-long",
         "traffic": "toy-jb-long", "chips": 1, "why": "toy"}]
    for metric in m["end_to_end"] + m["per_layer"]:
        if metric["name"] in ("itl_p95_ms", "serve_tokens_per_s",
                              "dummy_count"):
            metric["workloads"] += ["toy-jb-serve", "toy-jb-long"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(m))
    assert manifest.problems(m, tmp) == []
    return tmp


def drive_cell(cell):
    line = run.drive(cell, jax.devices()[:1], PEAKS,
                     helpers.run_args(seed=SEED, seconds=SECONDS),
                     run.Phases())
    return line, line["compared"]["served_over_control"]


@pytest.mark.parametrize("name", ["toy-jb-serve", "toy-jb-long"])
def test_the_toy_cell_runs_and_compares_correct(root, name):
    line = drive(root, name, seed=SEED, seconds=SECONDS)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["itl_p95_ms"]["value"] > 0


def test_a_token_altered_is_not_correct(root):
    line = drive(root, "toy-jb-serve", fault="token_altered",
                 seed=SEED, seconds=SECONDS)
    assert line["correct"] is False


def test_the_serve_control_is_not_correct(root):
    """`quant="int8_fwd"`: the mixers' and the attention's projections,
    the MLP and the tied head through the int8 contraction."""
    cell = manifest.Cell(manifest.load(root), "toy-jb-serve", root)
    cell.mix["quant"] = "int8_fwd"
    line, c = drive_cell(cell)
    assert line["correct"] is False
    assert c["value"] > c["limit"], line["compared"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_planted_in_the_program_is_not_correct(root, fault,
                                                       monkeypatch):
    name = "toy-jb-long" if fault == "bf16_state" else "toy-jb-serve"
    cell = manifest.Cell(manifest.load(root), name, root)
    fam = cell.family
    assert fault in fam.FAULTS
    FAULTS[fault](monkeypatch)
    true_model = fam.program_model
    extra = 16 * (1 + list(FAULTS).index(fault))

    def faulty(cfg, mix):
        # a longer table: a configuration of its own
        model = true_model(cfg, mix)
        return model.clone(cfg=dataclasses.replace(
            model.cfg, max_seq_len=model.cfg.max_seq_len + extra))

    monkeypatch.setattr(fam, "program_model", faulty)
    line, c = drive_cell(cell)
    assert line["correct"] is False
    assert c["value"] > c["limit"], line["compared"]


def test_the_rows_reference_reads_what_the_whole_one_reads(root):
    """The driver's reference (logits of the served positions alone, no
    position past the last of them computed) against
    `reference.ServeReference` (whole logits) on one request: the same
    gaps, the program's and the control's."""
    cell = manifest.Cell(manifest.load(root), "toy-jb-serve", root)
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
    """Each of `FAULTS` planted in the reference moves its logits where
    the fault can act: a bf16 state everywhere, a carry lost at the
    chunk's edges past the first chunk, the norms left out everywhere."""
    fam = manifest.load_family(manifest.BENCH_DIR, "jamba")
    cfg = dict(TOY_JB_CONFIG, compute_dtype="float32")
    w = fam.make_weights(cfg, reference.seed_u32(3))
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (1, 96)), jnp.int32)
    ref = fam.forward(cfg, w, tokens)
    for fault in fam.FAULTS:
        got = fam.forward(cfg, w, tokens, fault=fault, chunk=32)
        gap = jnp.abs(got - ref).max(-1)[0] / jnp.abs(ref).max()
        assert float(gap[32:].max()) > 1e-4, fault
        if fault in ("state_not_carried", "conv_not_carried"):
            # the first chunk alike (blocks of other lengths: rounding)
            assert float(gap[:32].max()) < 1e-5, fault


# -- the counts, at the published widths ----------------------------------

CELL = "jamba3b-longctx-steady"
NEW = ("ssm_scan_roofline", "decode_tick_roofline.ssm",
       "ssm_scan_busy_share")
ENGINE = {"ticks": 100, "ssm_states_read": 100 * 26 * 60.0,
          "ssm_states_written": 100 * 26 * 64.0}


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load(), CELL)


def test_the_counts_at_the_published_widths(cell):
    fam, cfg = cell.family, cell.config
    # every published key as the catalog gives it; nothing cut
    for key, value in (("hidden_size", 2560), ("num_attention_heads", 20),
                       ("num_key_value_heads", 1), ("num_hidden_layers", 28),
                       ("attn_layer_period", 14), ("attn_layer_offset", 7),
                       ("intermediate_size", 8192), ("mamba_d_state", 16),
                       ("mamba_d_conv", 4), ("mamba_dt_rank", 160),
                       ("mamba_expand", 2), ("num_experts", 1),
                       ("vocab_size", 65536), ("tie_word_embeddings", True),
                       ("served_positions", 34816)):
        assert cfg[key] == value, key
    assert cfg["reduced"] == []
    assert cfg["assumed"] and cfg["not_built"] and cfg["deployment"]
    kinds = fam.layer_kinds(cfg)
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert fam.period(cfg) == ("mamba",) * 7 + ((False, 0),) + (
        "mamba",) * 6
    # a mamba mixer: in 26.21 M, conv 0.03 M, x 0.98 M, dt 0.82 M, A_log
    # and D 0.09 M, norms, out 13.11 M
    assert fam.mamba_params(cfg) == (
        2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 + 16 + 16
        + 160 * 5120 + 5120 + 16 * 5120 + 5120 + 5120 * 2560) == 41_241_792
    assert fam.mlp_params(cfg) == 3 * 2560 * 8192 == 62_914_560
    assert fam.attention_params(cfg) == 2 * 2560 * 2560 + 2 * 2560 * 128
    assert fam.total_params(cfg) == cfg["params"] == 3_029_337_472
    assert abs(fam.total_params(cfg) / 3.03e9 - 1) < 0.001     # the issue's
    # 1,024 B a position (two attention layers, K and V of one head of
    # 128), 9.3 MB a stream of state whatever its length
    assert fam.kv_bytes_per_position(cfg) == 1024
    assert fam.state_bytes(cfg) == 16 * 5120 * 4 + 3 * 5120 * 2 == 358_400
    assert fam.stream_state_bytes(cfg) == 26 * 358_400 == 9_318_400
    # weights once: the matrices in bf16 and the float32 leaves
    wide = 28 * 2 * 2560 + 2560 + 26 * (5120 + 160 + 32 + 5120
                                        + 16 * 5120 + 5120)
    assert fam.decode_weight_bytes(cfg) == (
        2 * (fam.total_params(cfg) - wide) + 4 * wide)
    assert abs(fam.decode_weight_bytes(cfg) / 6.06e9 - 1) < 0.001
    # a tick at 64 streams of 8,192: 7.8 GB (the issue's reckoning)
    tick = fam.decode_tick_bytes(cfg, [8192] * 64, 2 * 26 * 64)
    assert tick == (fam.decode_weight_bytes(cfg) + 2 * 64 * 9_318_400
                    + 64 * 8192 * 1024)
    assert abs(tick / 7.8e9 - 1) < 0.01
    # the kernel's bytes for a chunk of 512: delta, u, y [512, 5120] and
    # B, C [512, 16] float32, and the state in and out, 26 layers
    assert fam.scan_bytes(cfg, 512) == 26 * 4 * (
        512 * (3 * 5120 + 32) + 2 * 16 * 5120)
    # FLOPs: the matrices a token passes through, the two attention layers
    # over its context, the tied head
    mats = 26 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560) + (
        2 * fam.attention_params(cfg) + 28 * 62_914_560)
    assert fam.forward_flops_token(cfg, 5000, head=False) == (
        2.0 * mats + 4.0 * 20 * 128 * 2 * 5000)
    assert (fam.forward_flops_token(cfg, 5000, head=True)
            - fam.forward_flops_token(cfg, 5000, head=False)
            == 2.0 * 2560 * 65536)
    assert fam.prefill_flops(cfg, 3) == pytest.approx(
        sum(fam.forward_flops_token(cfg, c, head=False)
            for c in (1, 2, 3)) + 2.0 * 2560 * 65536)


def test_the_cell_is_the_issues(cell):
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    for joined in ("step_mfu.serve", "decode_tick_ms", "prefill_chunk_ms",
                   "backlog_growth_rps", "ttft_p50_steady_ms", "ttft_p95_ms",
                   "queue_wait_p50_ms", "queue_wait_p95_ms",
                   "prefill_span_p50_ms", "preemptions", "ring_itl_p95_ms",
                   "tick_call_host_ms", "compiles_in_window"):
        assert joined in names
    # GPT-2's readers count every layer at the whole context; the pool
    # shares move an end-to-end metric the cell does not report
    for other in ("decode_tick_roofline", "paged_attn_roofline",
                  "kv_pool_in_use_share", "batch_occupancy",
                  "decode_tick_roofline.sparse"):
        assert other not in names
    assert {m["name"] for m in cell.end_to_end} == {"itl_p95_ms", "setup_s"}
    assert cell.chips == 1 and len(cell.entry["why"]) <= 200
    mix = cell.mix
    assert mix["kind"] == "serve_open_loop_rows"
    assert mix["rate_rps"] == pytest.approx(0.8 * mix["knee_rps"])
    assert (mix["ramp_s"], mix["trace_s"], mix["drain"]) == (30, 4, True)
    assert mix["compare_requests"] == 4 and mix["greedy"]
    assert mix["arrivals"] == {"dist": "exponential"}
    assert mix["engine"] == {"num_slots": 64, "block_size": 16,
                             "prefill_chunk": 512, "prefix_cache": False}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                    "sigma": 0.9, "min": 512, "max": 32768}
    assert mix["answer_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.6, "min": 128, "max": 2048}
    assert (mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"]
            == cell.config["served_positions"])
    assert list(mix["limits"]) == ["served_over_control"]


def ctx_for(cell, engine, scan_s=0.3):
    rec = types.SimpleNamespace(prompt_len=5000,
                                token_times=[0.5 + 0.01 * j
                                             for j in range(100)])
    ticks = [types.SimpleNamespace(dur=12e6) for _ in range(40)]
    chunks = [types.SimpleNamespace(dur=30e6) for _ in range(20)]
    trace = types.SimpleNamespace(
        program_runs=lambda name: ticks if "tick" in name else chunks,
        scope_time=lambda scope, rs=None: scan_s if scope == "ssm_scan"
        else 0.0,
        busy_s=lambda: 3.0)
    return types.SimpleNamespace(
        config=cell.config, mix=cell.mix, family=cell.family,
        peaks=peaks.lookup("TPU v5 lite"), trace=trace,
        trace_span=(0.0, 2.0), records=[rec],
        counters={"engine": engine})


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_or_returns_none(cell, name):
    got = cell.reader(name)(ctx_for(cell, ENGINE))
    assert got is not None and 0 < got <= 100
    # nothing to read: no counters (an older program), no traced tick or
    # chunk, no scan kernel in the trace
    empty = ctx_for(cell, {"ticks": 100}, scan_s=0.0)
    empty.trace.program_runs = lambda name: []
    assert cell.reader(name)(empty) is None


def test_the_readers_give_what_the_counts_say(cell):
    ctx = ctx_for(cell, ENGINE)
    fam, cfg = cell.family, cell.config
    # 20 traced chunks of 512, 0.3 s under the kernel's scope
    assert cell.reader("ssm_scan_roofline")(ctx) == pytest.approx(
        100 * 20 * fam.scan_bytes(cfg, 512) / 819e9 / 0.3)
    assert cell.reader("ssm_scan_busy_share")(ctx) == pytest.approx(10.0)
    # 99 ticks of one stream at contexts 5,001..5,099 in 40 traced runs of
    # 12 ms; 124 layer states moved a tick
    contexts = list(range(5001, 5100))
    nbytes = (fam.decode_tick_bytes(cfg, contexts, 40 * 26 * 124.0)
              + 39 * fam.decode_weight_bytes(cfg))
    assert cell.reader("decode_tick_roofline.ssm")(ctx) == pytest.approx(
        100 * nbytes / 819e9 / 0.48)
