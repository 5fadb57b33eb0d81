"""What the tests share: a temporary copy of the benchmark with a tiny
configuration, two tiny mixes, their cells, a dummy per-layer metric and
a second family of models that is not GPT-2-shaped (`toy_rope`: its
family file, a configuration, a train and a serve mix, two cells) added
as NEW files and entries, nothing that is there edited. That the harness
runs them is itself the proof that a later PR can add a cell, a mix, a
configuration, a metric and a kind of model without an edit."""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "model_type": "gpt2", "activation_function": "gelu_new",
    "initializer_range": 0.02, "layer_norm_epsilon": 1e-05,
    "n_positions": 128, "n_inner": None, "vocab_size": 128,
    "n_embd": 64, "n_head": 4, "n_layer": 2,
    "source": "a toy for the CPU tests; no published model"}

TINY_TRAIN = {
    "kind": "train", "strategy": "dp", "mesh": {"data": 1},
    "rows_per_chip": 4, "seq_len": 32, "attention": "dense",
    "remat": False, "scan_layers": False,
    "optimizer": {"name": "adamw", "lr": 3e-4, "b1": 0.9, "b2": 0.999,
                  "eps": 1e-8, "weight_decay": 1e-4},
    "run_ahead": 2, "trace_s": 1,
    "reference_block_rows": 2, "programs": {"step": "jit_step"},
    "attention_scope": "attn",
    "limits": {"loss2_gap": 1e-3, "loss3_gap": 1e-3,
               "grad_gap": 0.05, "grad_diff": 0.025,
               "delta_gap": 0.05}}

TINY_FSDP4 = dict(TINY_TRAIN, strategy="fsdp", mesh={"data": 1, "fsdp": 4},
                  rows_per_chip=2, scan_layers=True)

TINY_SERVE = {
    "kind": "serve_open_loop", "rate_rps": 8.0, "ramp_s": 0.5,
    "arrivals": {"dist": "exponential"},
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.4,
                      "min": 8, "max": 60},
    "answer_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.4,
                      "min": 4, "max": 16},
    "engine": {"num_slots": 4, "block_size": 16}, "greedy": True,
    "drain": True, "first_token_timeout_s": 20, "trace_s": 1, "compare_requests": 4,
    "programs": {"tick": "jit_paged_decode_tick",
                 "chunk": "jit_paged_prefill_chunk"},
    "attention_scope": "attn", "limits": {"served_mean_gap": 0.001}}

# a toy wide enough that the program's own int8 path reads apart from its
# bf16 path in the served tokens (PERF.md, section 4)
TOY_SERVE_CONFIG = dict(TINY_CONFIG, n_embd=256, n_head=4, n_layer=6,
                        vocab_size=8192, n_positions=256)
TOY_SERVE = dict(
    TINY_SERVE, rate_rps=6.0, compare_requests=12,
    answer_tokens={"dist": "lognormal", "median": 40, "sigma": 0.3,
                   "min": 16, "max": 64},
    limits={"served_mean_gap": 6e-5})

# the second family: the program's RoPE / RMSNorm / SwiGLU / no-bias /
# untied-head dialect with grouped-query heads, wide enough that its
# serve cell's int8 control reads apart too
TOY_ROPE_CONFIG = {
    "model_type": "toy_rope", "hidden_act": "silu",
    "initializer_range": 0.02, "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "max_position_embeddings": 256,
    "vocab_size": 8192, "hidden_size": 256, "intermediate_size": 512,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 6, "tie_word_embeddings": False,
    "source": "a toy for the CPU tests; no published model"}
TOY_ROPE_TRAIN = dict(TINY_TRAIN, limits={
    "loss2_gap": 1e-3, "loss3_gap": 1e-3, "grad_gap": 0.05,
    "grad_diff": 0.025, "delta_gap": 0.05})
TOY_ROPE_SERVE = dict(TOY_SERVE, limits={"served_mean_gap": 3.8e-4})

DUMMY_READER = '''"""A dummy per-layer metric: steps or requests attempted."""


def read(ctx):
    return getattr(ctx, "steps", None) or len(getattr(ctx, "records", []))
'''


def temp_benchmark(tmp: pathlib.Path, fsdp4: bool = False) -> pathlib.Path:
    """Copy BENCHMARK.json and benchmark/ to `tmp` and ADD the tiny
    files and entries."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp / "benchmark",
                    ignore=shutil.ignore_patterns(
                        "__pycache__", ".jax_cache", ".trace"))
    before = {p: p.read_bytes() for p in (tmp / "benchmark").rglob("*")
              if p.is_file()}
    b = tmp / "benchmark"
    (b / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    (b / "traffic" / "tiny-train.json").write_text(json.dumps(TINY_TRAIN))
    (b / "traffic" / "tiny-serve.json").write_text(json.dumps(TINY_SERVE))
    (b / "traffic" / "tiny-fsdp4.json").write_text(json.dumps(TINY_FSDP4))
    (b / "configs" / "toy-serve.json").write_text(
        json.dumps(TOY_SERVE_CONFIG))
    (b / "traffic" / "toy-serve.json").write_text(json.dumps(TOY_SERVE))
    (b / "metrics" / "dummy_count.py").write_text(DUMMY_READER)
    shutil.copy(ROOT / "benchmark" / "tests" / "data" / "toy_rope.py",
                b / "families" / "toy_rope.py")
    (b / "configs" / "toy-rope.json").write_text(
        json.dumps(TOY_ROPE_CONFIG))
    (b / "traffic" / "toy-rope-train.json").write_text(
        json.dumps(TOY_ROPE_TRAIN))
    (b / "traffic" / "toy-rope-serve.json").write_text(
        json.dumps(TOY_ROPE_SERVE))
    m = json.loads((tmp / "BENCHMARK.json").read_text())
    m["configs"].append({
        "name": "tiny", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/tiny.json", "reduced": [],
        "why": "a toy"})
    m["configs"].append({
        "name": "toy-serve", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/toy-serve.json", "reduced": [],
        "why": "a toy"})
    m["configs"].append({
        "name": "toy-rope", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/toy-rope.json", "reduced": [],
        "why": "a toy of another family"})
    m["workloads"].append({"name": "toy-serve", "config": "toy-serve",
                           "traffic": "toy-serve", "chips": 1,
                           "why": "toy"})
    for cell in ("toy-rope-train", "toy-rope-serve"):
        m["workloads"].append({"name": cell, "config": "toy-rope",
                               "traffic": cell, "chips": 1,
                               "why": "toy"})
    cells = [("tiny-train", 1), ("tiny-serve", 1)]
    if fsdp4:  # a second four-chip cell: only the tests of `correct` ask
        cells.append(("tiny-fsdp4", 4))
    for cell, chips in cells:
        m["workloads"].append({"name": cell, "config": "tiny",
                               "traffic": cell, "chips": chips,
                               "why": "toy"})
    for metric in m["end_to_end"]:
        if metric["name"] == "train_tokens_per_s":
            metric["workloads"] += [c for c, _ in cells
                                    if c != "tiny-serve"]
            metric["workloads"].append("toy-rope-train")
        if metric["name"] in ("itl_p95_ms",
                              "serve_tokens_per_s"):
            metric["workloads"] += ["tiny-serve", "toy-serve",
                                    "toy-rope-serve"]
    m["per_layer"].append({
        "name": "dummy_count", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry",
        "moves": "setup_s",
        "workloads": [c for c, _ in cells] + [
            "toy-serve", "toy-rope-train", "toy-rope-serve"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(m))
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
    return tmp


def run_args(seed=7, seconds=1.0, trace=0):
    return argparse.Namespace(workload=None, seed=seed, seconds=seconds,
                              trace=trace, set=[])
