"""The readers and counts this family brought: each returns a number
where the program's counters are there and `None` (never 0, never an
error) where they are not, as on a commit older than the counters; the
sparse tick's bytes follow what is attended, not the context."""

import json
import types

import pytest

from benchmark import manifest, peaks

CELL = "dots3-longdoc-steady"
NEW = ("decode_tick_roofline.sparse", "moe_load_max_over_mean",
       "moe_held_assignment_share", "sparse_selected_share",
       "window_pool_in_use_share", "latent_pool_in_use_share")
ENGINE = {"ticks": 100, "moe_experts_hit": 6000.0, "moe_load_max": 900.0,
          "moe_load_mean": 300.0, "moe_assignments_held": 38400.0,
          "moe_assignments_total": 307200.0, "sparse_selected": 2.0e6,
          "sparse_live": 9.0e6, "block_utilization": 0.41,
          "window_block_utilization": 0.77}


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load(), CELL)


def ctx_for(cell, engine):
    rec = types.SimpleNamespace(prompt_len=8000,
                                token_times=[0.5 + 0.01 * j
                                             for j in range(100)])
    runs = [types.SimpleNamespace(dur=30e6) for _ in range(40)]
    trace = types.SimpleNamespace(program_runs=lambda name: runs)
    return types.SimpleNamespace(
        config=cell.config, mix=cell.mix, family=cell.family,
        peaks=peaks.lookup("TPU v5 lite"), trace=trace,
        trace_span=(0.0, 2.0), records=[rec],
        counters={"engine": engine})


def test_the_cell_lists_the_new_readers(cell):
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    assert "decode_tick_roofline" not in names  # counts a dense cache
    assert "paged_attn_roofline" not in names


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_or_returns_none(cell, name):
    got = cell.reader(name)(ctx_for(cell, ENGINE))
    assert got is not None and got > 0
    if cell.per_layer[[m["name"] for m in cell.per_layer].index(name)][
            "unit"] == "%":
        assert got <= 100
    # a program without the counters (the parent commit): nothing to read
    old = {"ticks": 100, "block_utilization": 0.41}
    assert cell.reader(name)(ctx_for(cell, old)) is None


def test_the_readers_give_what_the_counters_say(cell):
    ctx = ctx_for(cell, ENGINE)
    assert cell.reader("moe_held_assignment_share")(ctx) == 12.5
    assert cell.reader("moe_load_max_over_mean")(ctx) == 3.0
    assert cell.reader("sparse_selected_share")(ctx) == pytest.approx(
        100 * 2 / 9)
    assert cell.reader("window_pool_in_use_share")(ctx) == 77.0
    assert cell.reader("latent_pool_in_use_share")(ctx) == 41.0


def test_a_ticks_bytes_follow_what_is_attended(cell):
    fam, cfg = cell.family, cell.config
    base = fam.decode_tick_bytes(cfg, [], 0.0)
    assert base == fam.dense_weight_bytes(cfg)
    # one stream at 8,192 positions: the indexer's keys of all of them in
    # the two full layers, 2,048 latent rows in each, 513 window rows in
    # each of the three sliding layers
    one = fam.decode_tick_bytes(cfg, [8192], 0.0) - base
    assert one == 2 * (8192 * 128 + 2048 * 576) * 2 + 3 * 513 * 1088 * 2
    # doubling the context adds only the indexer's keys
    two = fam.decode_tick_bytes(cfg, [16384], 0.0) - base
    assert two - one == 2 * 8192 * 128 * 2
    # an expert that a live token chose is read once
    assert (fam.decode_tick_bytes(cfg, [], 5.0) - base
            == 5 * fam.expert_bytes(cfg))
    assert fam.decode_weight_bytes(cfg) == base + 4 * 32 * fam.expert_bytes(
        cfg)
    # the configuration's file says what it counts
    assert json.loads((manifest.BENCH_DIR / "configs"
                       / "dots3-note-prev.json").read_text())[
        "params"] == fam.total_params(cfg)
