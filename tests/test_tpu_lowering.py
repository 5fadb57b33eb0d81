"""Tripwires for "does it start on the chip", from a host that has none.

libtpu is installed beside JAX, so lowering with
``lowering_platforms=("tpu",)`` runs the real Pallas→Mosaic lowering on
the CPU, and `jax.experimental.topologies` hands out abstract v5e devices
against which ``.compile()`` runs the real XLA:TPU + Mosaic compile.
Neither touches a chip, and neither is a measurement.

Tier-1 (seconds): the three failures PR 21 found this way, kept as
tests — the paged decode kernel's block specs (refused by the (8, 128)
tiling rule), the flash kernel bare under a multi-device jit ("Mosaic
kernels cannot be automatically partitioned") and tp at GPT-2's odd
published vocabulary — plus what keeps a CPU from passing for a chip:
no backend on import, ``--backend tpu`` and `chip_smoke.py` refusing a
CPU, and the compile cache's one placement rule.

The full-width compiles against the v5e topology (~7 s each on 8 cores)
are marked ``slow``, out of the quick tier.

CPU tests run the kernels interpreted (the default where the backend is
not a TPU); ``compiled_kernels`` flips that default the way a chip would.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorchdistributed_tpu.models import GPT2, gpt2_config
from pytorchdistributed_tpu.ops.pallas_attention import (
    chunk_attention,
    flash_attention,
    paged_flash_attention,
)
from pytorchdistributed_tpu.runtime.mesh import create_mesh
from pytorchdistributed_tpu.training import Trainer, token_cross_entropy_loss

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MARKER = "tpu_custom_call"
TPU = ("tpu",)

# GPT-2 small: the train step's attention shapes and the serving tick's
HEADS, HEAD_DIM, SEQ, BATCH = 12, 64, 1024, 8
SLOTS, BLOCK = 8, 16


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Every kernel entry point picks interpret mode from
    ``jax.default_backend() != "tpu"``; answer as a chip would."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def lower_for_tpu(fn, *args) -> str:
    return jax.jit(fn).trace(*args).lower(lowering_platforms=TPU).as_text()


def v5e_devices(n: int):
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[:n]


# ---------------------------------------------------------------------------
# kernels


def flash_fwd_bwd(q, k, v, g):
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False), q, k, v)
    return (out, *vjp(g))


def test_flash_fwd_bwd_lowers_for_tpu():
    x = sds((BATCH, SEQ, HEADS, HEAD_DIM))
    assert lower_for_tpu(flash_fwd_bwd, x, x, x, x).count(MARKER) == 3


def paged_args(heads=HEADS, kv_heads=None, int8=False, slots=SLOTS,
               layers=0, head_dim=HEAD_DIM, pages=SEQ // BLOCK, blocks=None,
               merged=False, window=0):
    """q, the two pools, tables, lengths, then the scale planes, the
    layer and the starts (None where the case has none), and the static
    window."""
    kv_heads = kv_heads or heads
    stack = (layers,) if layers else ()
    nb = blocks or slots * pages + 1
    pool = sds(stack + (nb, BLOCK, kv_heads * head_dim),
               jnp.int8 if int8 else jnp.bfloat16)
    scales = sds(stack + (nb, BLOCK, kv_heads), jnp.float32) if int8 else None
    per_slot = sds((slots,), jnp.int32)
    return [sds((slots, heads, head_dim)), pool, pool,
            sds((slots, pages), jnp.int32), per_slot,
            scales, scales, sds((), jnp.int32) if layers else None,
            per_slot if merged else None, window]


def paged(q, kp, vp, tables, lengths, ks, vs, layer, starts, window=0):
    # a call whose caller merges it with another's gives `starts` and
    # asks for the log-sum-exp
    return paged_flash_attention(q, kp, vp, tables, lengths, k_scale=ks,
                                 v_scale=vs, layer=layer, starts=starts,
                                 return_lse=starts is not None,
                                 window_tokens=window, interpret=False)


# EvaByte's pools in `evabyte-longgen-saturated`: 8 layers x 2,049 blocks
# of 16 rows of 32 heads x 128 lanes (16 KB a row), 16 slots
EVA_POOL = dict(heads=32, head_dim=128, slots=16, layers=8, blocks=2049,
                merged=True)
ST_POOL = dict(heads=28, kv_heads=4, head_dim=128, slots=32,
               pages=16384 // BLOCK)
CA_POOL = dict(heads=128, kv_heads=8, head_dim=128, slots=32,
               pages=33792 // BLOCK)
PAGED_CASES = {
    "bf16": dict(), "int8": dict(int8=True), "gqa": dict(kv_heads=HEADS // 3),
    # the serve cells' own call: gpt2-medium's layer-stacked pool (24 x
    # 2,049 blocks of 16 rows of 1,024 lanes), 32 slots of 64 table
    # entries, the layer traced
    "cell": dict(heads=16, slots=32, layers=24),
    # an EVA tick's two calls (ISSUE 35): the window pool through a
    # table of 32,768 positions from a tumbling start, the summary pool
    # through one of 2,048 rows, each with its log-sum-exp for the merge
    "eva_window": dict(EVA_POOL, pages=32768 // BLOCK),
    "eva_summary": dict(EVA_POOL, pages=2048 // BLOCK),
    # SmallThinker's two calls in `smallthinker-mixedlen-steady` (ISSUE
    # 36): 28 query heads over 4 key heads of 128 (rows of 512 lanes, a
    # group of 7 queries a key head), 32 slots of 1,024 table entries; the
    # full layers' pool from the first row, the window layers' under a
    # sliding window of 4,096
    "grouped_full": dict(ST_POOL, layers=2, blocks=32769),
    "grouped_window": dict(ST_POOL, layers=6, blocks=8546, window=4096),
    # Command A+'s two calls in `cmdaplus-rag-steady`: 128 query heads
    # over 8 key heads of 128 (rows of 1,024 lanes, a group of 16 queries
    # a key head), 32 slots of 2,112 table entries; one full layer's pool
    # and three window layers' under a sliding window of 4,096
    "wide_group_full": dict(CA_POOL, layers=1, blocks=32769),
    "wide_group_window": dict(CA_POOL, layers=3, blocks=8546, window=4096),
}


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_decode_kernel_lowers_for_tpu(case):
    """The pools stay in HBM and the kernel copies whole pool rows,
    ``(block_size, kv_heads*head_dim)`` a block (768 lanes, 256 for the
    GQA group, 1,024 in the GPT-2 serve cells, 4,096 in EvaByte's, 512
    in SmallThinker's), by its own DMAs."""
    *args, window = paged_args(**PAGED_CASES[case])
    assert MARKER in lower_for_tpu(
        functools.partial(paged, window=window), *args)


@pytest.mark.slow
@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_decode_kernel_compiles_for_v5e(case):
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(v5e_devices(1)[0])
    *args, window = paged_args(**PAGED_CASES[case])
    args = [None if a is None
            else jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
            for a in args]
    assert MARKER in jax.jit(functools.partial(paged, window=window)).lower(
        *args).compile().as_text()


# Command A+'s chunk in `cmdaplus-rag-steady`: 512 queries of 128 heads
# over 8 key heads of 128, read from rows gathered in whole blocks of 512:
# its full layer's at the longest context (33,792 positions) and a window
# layer's (4,096 + 511 positions and a block, made whole)
CHUNK_CASES = {"full": (33792, 0), "window": (5120, 4096)}


def chunk_args(rows):
    return (sds((1, 512, 128, 128)), sds((1, rows, 1024)),
            sds((1, rows, 1024)), sds((1, 512), jnp.int32),
            sds((1, rows), jnp.int32))


@pytest.mark.parametrize("case", CHUNK_CASES)
def test_chunk_kernel_lowers_for_tpu(case):
    """The chunk's flash kernel: a block of keys is a kv head's 128 lanes
    of the gathered rows where they lie, the positions ride as blocks
    beside them."""
    rows, window = CHUNK_CASES[case]
    assert lower_for_tpu(functools.partial(
        chunk_attention, window=window, interpret=False),
        *chunk_args(rows)).count(MARKER) == 1


@pytest.mark.slow
@pytest.mark.parametrize("case", CHUNK_CASES)
def test_chunk_kernel_compiles_for_v5e(case):
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(v5e_devices(1)[0])
    rows, window = CHUNK_CASES[case]
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
            for a in chunk_args(rows)]
    compiled = jax.jit(functools.partial(
        chunk_attention, window=window, interpret=False)).lower(
            *args).compile()
    assert MARKER in compiled.as_text()
    # nothing of the rows is copied: no score, no transposed key
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6


# ---------------------------------------------------------------------------
# the train step under a mesh


def lm_batch(batch, seq):
    return {"tokens": np.zeros((batch, seq), np.int32),
            "targets": np.zeros((batch, seq), np.int32)}


MESHES = {"dp": dict(data=4), "fsdp": dict(data=1, fsdp=4),
          "tp": dict(data=2, tensor=2), "tp_fsdp": dict(data=1, fsdp=2,
                                                        tensor=2)}


def trainer_on(devices, strategy, cfg, **kw):
    return Trainer(GPT2(cfg), optax.adamw(1e-4), token_cross_entropy_loss,
                   mesh=create_mesh(devices=devices, **MESHES[strategy]),
                   strategy=strategy, **kw)


@pytest.mark.parametrize("strategy", ["dp", "fsdp"])
def test_train_step_with_flash_lowers_on_four_devices(compiled_kernels,
                                                      strategy):
    """XLA cannot partition a Mosaic kernel: bare under a 4-device jit the
    flash call is refused at lowering; inside shard_map it lowers. The
    interpreted kernel is ordinary HLO, which is why no CPU run of the
    step could see this."""
    cfg = gpt2_config("test", attention="pallas")
    trainer = trainer_on(jax.devices()[:4], strategy, cfg)
    text = trainer.lower_step(lm_batch(8, 64),
                              platforms=TPU).as_text()
    assert text.count(MARKER) == 3  # fwd, dKV, dQ inside the layer scan


def test_tp_shards_what_divides_at_published_vocab():
    """GPT-2's vocabulary (50,257) is odd: under vocab → tensor the
    embedding stays replicated over that axis rather than failing jit's
    even-sharding rule; everything that divides is still split."""
    from pytorchdistributed_tpu.parallel.sharding import (
        shardings_for_strategy,
    )

    cfg = gpt2_config("small", num_layers=1)
    mesh = create_mesh(devices=jax.devices()[:4], **MESHES["tp"])
    boxed = jax.eval_shape(GPT2(cfg).init, jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))
    shardings = shardings_for_strategy("tp", boxed, mesh)["params"]
    assert tuple(shardings["embed"]["tok"]["embedding"].spec) == (None,
                                                                  None)
    wi = shardings["h"]["block"]["mlp"]["wi"]["kernel"]
    assert "tensor" in jax.tree.leaves(tuple(wi.spec))


@pytest.mark.slow
@pytest.mark.parametrize("strategy", ["dp", "fsdp", "tp", "tp_fsdp"])
def test_full_width_train_step_compiles_for_v5e(compiled_kernels, strategy):
    cfg = gpt2_config("small", attention="pallas")
    trainer = trainer_on(
        v5e_devices(4), strategy, cfg,
        compiler_options={"xla_tpu_scoped_vmem_limit_kib": "24576"})
    compiled = trainer.lower_step(
        lm_batch(BATCH, SEQ)).compile()
    assert MARKER in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


# ---------------------------------------------------------------------------
# the serving tick


def test_engine_default_tick_lowers_the_paged_kernel(compiled_kernels):
    """On a TPU ``paged_attn`` resolves to the kernel, and the decode
    tick the engine dispatches really holds it."""
    from pytorchdistributed_tpu.serving import ServingEngine

    model = GPT2(gpt2_config("test", embed_dim=128, num_heads=2))
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    engine = ServingEngine(model, params, num_slots=2, block_size=16)
    assert engine.summary()["paged_attn"] == "pallas"
    assert MARKER in engine.lower_tick(platforms=TPU).as_text()


def test_eva_tick_lowers_the_kernel_once_a_pool(compiled_kernels):
    """On a TPU a model whose two pools both hold per-head key and value
    rows of whole lane tiles (EVA: the window's rows, the chunks'
    summaries) ticks through the kernel too: the scanned layer holds two
    calls of it, one a pool, and no gather of a whole window."""
    from benchmark import manifest, reference
    from pytorchdistributed_tpu.serving import ServingEngine
    from tests.test_eva_serving import wide_toy

    fam = manifest.load_family(manifest.BENCH_DIR, "evabyte")
    toy = wide_toy()
    w = jax.jit(lambda s: fam.make_weights(toy, s))(reference.seed_u32(35))
    engine = ServingEngine(fam.program_model(toy, {}),
                           fam.to_program_tree(w, toy, {}), num_slots=2,
                           block_size=16, prefill_chunk=16,
                           prefix_cache=False)
    assert engine.summary()["paged_attn"] == "pallas"
    assert engine.lower_tick(platforms=TPU).as_text().count(MARKER) == 2
    engine.close()


@pytest.mark.parametrize("steps,channels", [(512, 5120), (7, 256)])
def test_ssm_scan_kernel_lowers_for_tpu(steps, channels):
    """The selective-scan kernel (ops/ssm_scan.py) at a chunk of Jamba's
    published widths (512 positions, 5,120 channels, 16 states) and at a
    ragged toy length: one Mosaic call, named so that a trace finds it."""
    from pytorchdistributed_tpu.ops import ssm_scan

    f32 = jnp.float32
    rows, bc = sds((1, steps, channels), f32), sds((1, steps, 16), f32)
    text = lower_for_tpu(
        functools.partial(ssm_scan.kernel_scan, interpret=False), rows,
        rows, bc, bc, sds((16, channels), f32), sds((1, 16, channels), f32))
    assert text.count(MARKER) == 1 and "ssm_scan" in text


def test_mamba_period_ticks_through_the_paged_kernel(compiled_kernels):
    """A period of mamba mixers and one NoPE attention layer whose rows
    are one 128-lane tile (one key/value head of 128): on a TPU the tick
    reads the attention pool through the paged kernel, and its states by
    XLA's one elementwise step (no scan kernel in a tick)."""
    from benchmark import manifest, reference
    from pytorchdistributed_tpu.serving import ServingEngine
    from tests.test_jamba_serving import TOY

    fam = manifest.load_family(manifest.BENCH_DIR, "jamba")
    toy = dict(TOY, hidden_size=256, num_attention_heads=2)   # heads of 128
    w = jax.jit(lambda s: fam.make_weights(toy, s))(reference.seed_u32(40))
    engine = ServingEngine(fam.program_model(toy, {}),
                           fam.to_program_tree(w, toy, {}), num_slots=2,
                           block_size=16, prefill_chunk=16,
                           prefix_cache=False)
    assert engine.summary()["paged_attn"] == "pallas"
    text = engine.lower_tick(platforms=TPU).as_text()
    assert text.count(MARKER) == 1 and "ssm_scan" not in text


@pytest.mark.parametrize("banks,compute", [("in_place", "bfloat16"),
                                           ("sliced", "float32")])
def test_period_tick_lowers_the_kernel_once_a_layer(compiled_kernels, banks,
                                                    compute):
    """On a TPU a model whose layers come in two kinds over two pools of
    per-head rows of whole lane tiles (SmallThinker: full layers, window
    layers, grouped heads) ticks through the kernel: the scanned period
    holds four calls of it, one a layer, and no gather of a window. Its
    experts' three grouped products a layer are kernels too (ISSUE 37:
    `ops/grouped_matmul.py`), and `summary()` says what they are handed:
    the stack's banks whole where the engine holds them in the type it
    computes in, as a cell's are; the scan's slice of them where not
    (the toy's bf16 leaves served in float32)."""
    from benchmark import manifest, reference
    from pytorchdistributed_tpu.serving import ServingEngine
    from tests.test_smallthinker_serving import TOY

    fam = manifest.load_family(manifest.BENCH_DIR, "smallthinker")
    toy = dict(TOY, head_dim=64, compute_dtype=compute)  # rows of 128 lanes
    w = jax.jit(lambda s: fam.make_weights(toy, s))(reference.seed_u32(36))
    engine = ServingEngine(fam.program_model(toy, {}),
                           fam.to_program_tree(w, toy, {}), num_slots=2,
                           block_size=16, prefill_chunk=16,
                           prefix_cache=False)
    assert engine.summary()["paged_attn"] == "pallas"
    assert engine.summary()["expert_banks"] == banks
    text = engine.lower_tick(platforms=TPU).as_text()
    # the twelve products call two lowered functions, one a shape of
    # projection (`kernel_product` is jitted): a kernel each
    assert text.count(MARKER) == 4 + 2 and "ragged_dot" not in text
    assert text.count("call @kernel_product") == 4 * 3
    engine.close()


def sorted_widths(text: str) -> list[int]:
    """The last axis of every `top_k` and `sort` operand of a lowered
    program."""
    shapes = re.findall(
        r"chlo\.top_k\([^)]*\)[^\n]*? : tensor<([0-9x]+)x\w+>", text)
    shapes += re.findall(
        r'"stablehlo\.sort"\(.*?\}\) : \(tensor<([0-9x]+)x\w+>', text,
        flags=re.S)
    return [int(shape.split("x")[-1]) for shape in shapes]


@pytest.mark.parametrize("vocab,sorts_it", [
    (320, True),        # EvaByte's: under the rule, today's program
    (50_257, False),    # GPT-2 medium's
    (151_936, False),   # SmallThinker's
])
def test_tick_sorts_no_row_of_a_wide_vocabulary(compiled_kernels, vocab,
                                                sorts_it):
    """The sampler's candidates (ISSUE 39: `inference._top_candidates`)
    are found by groups where the vocabulary is wider than the
    `candidates * _GROUP` numbers sorted anyway, and by `lax.top_k` over
    the row where not; the choice is static, so the lowered tick says
    whether it engaged: no `top_k` or `sort` whose operand's last axis is
    the vocabulary, none wider than the chosen groups' `candidates *
    _GROUP` members and the numbers past the last whole group."""
    from pytorchdistributed_tpu import inference
    from pytorchdistributed_tpu.serving import ServingEngine

    model = GPT2(gpt2_config("test", embed_dim=128, num_heads=2,
                             num_layers=1, vocab_size=vocab))
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    engine = ServingEngine(model, params, num_slots=2, block_size=16)
    widths = sorted_widths(engine.lower_tick(platforms=TPU).as_text())
    assert widths, "the tick holds no top_k at all"
    if sorts_it:
        assert widths == [vocab]
    else:
        c, g = engine.candidates, inference._GROUP
        assert sorted(widths) == [
            c,                      # the chosen groups' ids
            vocab // g,             # the whole groups' maxima
            c * g + vocab % g]      # their members and the row's last few
        assert max(widths) < (c + 1) * g < vocab
    engine.close()


def test_engine_keeps_the_kernel_to_rows_of_whole_lane_tiles(
        compiled_kernels):
    """The kernel's own copies move whole 128-lane tiles (Mosaic refuses
    a DMA slice of any other width: found by the v5e compile of the int8
    scale planes, ISSUE 33), so on a TPU the default is the kernel only
    where ``kv_heads*head_dim`` is such a row, and asking for it
    elsewhere is refused when the engine is built, not when the first
    tick compiles."""
    from pytorchdistributed_tpu.serving import ServingEngine

    model = GPT2(gpt2_config("test"))              # rows of 64 lanes
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    engine = ServingEngine(model, params, num_slots=2, block_size=16)
    assert engine.summary()["paged_attn"] == "gather"
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        ServingEngine(model, params, num_slots=2, block_size=16,
                      paged_attn="pallas")


# One layer's pool at the serve cells' geometry: gpt2-medium width, 32
# slots of 64 blocks of 16 tokens plus the trash block (67 MB in bf16).
POOL_SLOTS, POOL_CHUNK = 32, 128


def serve_program_for_v5e(program: str, model, slots: int, blocks: int,
                          chunk: int = POOL_CHUNK, planes: bool = False):
    """The engine's jitted tick or prefill chunk (of `chunk` tokens) of
    `model` over pools of `blocks` blocks, compiled for one abstract v5e
    chip; with `planes`, on the tree as the engine serves it (a scanned
    stack's fused kernels as planes: serving/weights.py:served)."""
    from jax.sharding import SingleDeviceSharding

    from pytorchdistributed_tpu.serving.engine import (
        paged_decode_tick,
        paged_prefill_chunk,
        paged_slot_models,
    )
    from pytorchdistributed_tpu.serving.weights import served

    one = SingleDeviceSharding(v5e_devices(1)[0])
    tick_model, chunk_model = paged_slot_models(
        model, slots, BLOCK, blocks, paged_attn="pallas")

    def arg(shape=(), dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    state = jax.tree.map(
        lambda leaf: arg(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: tick_model.init(
            jax.random.key(0), jnp.zeros((slots, 1), jnp.int32))))
    if planes:
        state["params"] = jax.eval_shape(
            lambda w: served(w, None, model.cfg.dtype)[0], state["params"])
    key = jax.eval_shape(lambda: jax.random.key_data(jax.random.key(0)))
    f32 = jnp.float32
    pages = {k.table: k.pages(tick_model.cfg.kv_pages)
             for k in tick_model.cfg.cache_kinds}
    if program == "tick":
        per_slot = (slots,)
        lowered = paged_decode_tick.lower(
            tick_model, state["params"], state["cache"],
            {t: arg((slots, n)) for t, n in pages.items()}, arg(per_slot),
            arg(per_slot),
            arg(per_slot + key.shape, key.dtype), arg(per_slot),
            arg(per_slot, f32), arg(per_slot), arg(per_slot, f32),
            candidates=64)
    else:
        lowered = paged_prefill_chunk.lower(
            chunk_model, state["params"], state["cache"],
            arg((1, chunk)), arg(),
            {t: arg((n,)) for t, n in pages.items()}, arg(),
            arg(key.shape, key.dtype), arg(), arg((), f32), arg(),
            arg((), f32), candidates=64)
    return lowered.compile()


@pytest.mark.parametrize("stack", ["scanned", "unrolled"])
@pytest.mark.parametrize("program", ["tick", "chunk"])
def test_serve_programs_move_nothing_pool_sized(compiled_kernels, program,
                                                stack):
    """A decode tick and a prefill chunk write their rows into the
    donated KV pool in place and read it through the kernel's (or the
    gather's) indices: the optimised v5e program holds no copy, slice,
    update-slice or fresh buffer as large as one layer's pool, and the
    output aliases both pools. (Before the pool was lane-dense and
    carried through the layer loop, every layer of every tick copied its
    67 MB of pool four times between two layouts.)"""
    cfg = dataclasses.replace(gpt2_config("medium"), num_layers=2,
                              scan_layers=stack == "scanned")
    blocks = POOL_SLOTS * (cfg.max_seq_len // BLOCK) + 1
    compiled = serve_program_for_v5e(program, GPT2(cfg), POOL_SLOTS, blocks)
    pool_elems = blocks * BLOCK * cfg.embed_dim
    assert not pool_sized_moves(compiled, pool_elems)
    pools = 2 * cfg.num_layers * pool_elems * 2  # K and V, bf16
    assert compiled.memory_analysis().alias_size_in_bytes >= pools


def pool_sized_moves(compiled, pool_elems: int) -> str:
    """The lines of a compiled program that copy, slice, transpose or
    allocate as many elements as one layer's pool (none, it is hoped)."""
    moved = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        # fusion bodies are lines of the same text, so a copy or a slice
        # inside a fusion is seen too
        if m and (m.group(2) in ("copy", "copy-start", "transpose",
                                 "dynamic-slice", "dynamic-update-slice")
                  or 'custom_call_target="AllocateBuffer"' in line):
            dims = [int(d) for d in m.group(1).split(",") if d]
            if int(np.prod(dims)) >= pool_elems:
                moved.append(line.strip()[:160])
    return "\n".join(moved)


def test_period_programs_read_both_pools_in_place(compiled_kernels):
    """SmallThinker's tick and chunk at the cell's widths (28 query heads
    over 4 key heads of 128, 64 experts of 768, the vocabulary of
    151,936, 32 slots, a full pool of 32,769 blocks and a window pool of
    8,546; two periods of four layers), compiled for one v5e chip: the
    tick holds the paged kernel once a layer, both pools are written and
    read in place (the outputs alias all four leaves), nothing as large
    as a layer's pool is moved, and a chunk's temporaries (the scores of
    a block of queries over the longest context) stay under half a GB.
    The experts' banks are read in place too (ISSUE 37): three grouped
    products a layer through the kernel of `ops/grouped_matmul.py`, none
    of XLA's own, and no copy, slice or fresh buffer as large as one
    layer's bank of 64 x 2,560 x 768, where `lax.ragged_dot` on the
    scan's slice had each of a period's twelve copied out of the stack
    (a scan of ONE period has one trip and shows no copy)."""
    import json

    from benchmark import manifest

    fam = manifest.load_family(manifest.BENCH_DIR, "smallthinker")
    with open(os.path.join(
            REPO, "benchmark/configs/smallthinker-21ba3b.json")) as f:
        cfg = json.load(f)
    assert cfg["num_hidden_layers"] == 8
    slots, full_blocks, window_blocks = 32, 32769, 8546
    model = fam.program_model(cfg, {})
    model = model.clone(cfg=dataclasses.replace(
        model.cfg, window_blocks=window_blocks))
    row = BLOCK * 512
    pools = 2 * 2 * 2 * row * (1 * full_blocks + 3 * window_blocks)  # bf16
    bank = 64 * 2560 * 768
    for program in ("tick", "chunk"):
        compiled = serve_program_for_v5e(program, model, slots,
                                         full_blocks, chunk=512)
        assert not pool_sized_moves(compiled, min(bank, window_blocks * row))
        text = compiled.as_text()
        assert "ragged-dot" not in text
        kernels = sum(1 for ln in text.splitlines() if MARKER in ln)
        # one period's: a paged call a layer in the tick, three products
        assert kernels == (4 if program == "tick" else 0) + 4 * 3
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= pools
        assert mem.temp_size_in_bytes < 0.5e9, (program,
                                                mem.temp_size_in_bytes)


def fused_slices(compiled, shapes) -> str:
    """The instructions of a compiled program, outside the bodies of its
    fusions, that make a buffer of one layer of a stacked fused kernel: a
    shape that holds the numbers of a ``[1, embed, c, width]`` of
    `shapes` in any order (none, it is hoped). A product that reads the
    layer where it lies has the stack's slice inside its own fusion."""
    want = {tuple(sorted((1,) + shape)) for shape in shapes}
    found, fused = [], False
    for line in compiled.as_text().splitlines():
        if line and not line[0].isspace():
            fused = "fused_computation" in line.split("(")[0]
            continue
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]", line)
        if m and not fused and tuple(sorted(
                int(d) for d in m.group(1).split(",") if d)) in want:
            found.append(line.strip()[:160])
    return "\n".join(found)


@pytest.mark.parametrize("program,planes", [("tick", True), ("chunk", True),
                                            ("tick", False)])
def test_served_planes_are_read_in_place(compiled_kernels, program, planes):
    """EvaByte's tick and chunk at the cell's widths (two layers),
    compiled for one v5e chip on the tree as the engine serves it: the
    scanned stack's fused gate/up and q/k/v as planes, `[layers, 2|3,
    embed, width]`, whose layer the products read where it lies, with no
    copy, transpose or slice of it first. In the checkpoint's layout
    (`[layers, embed, 2|3, width]`, the fused axis second-minor) the same
    tick copies each layer's 90 MB of gate/up out of the stack before its
    product: the test sees what it is meant to see."""
    import json

    from benchmark import manifest

    fam = manifest.load_family(manifest.BENCH_DIR, "evabyte")
    with open(os.path.join(REPO, "benchmark/configs/evabyte.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=2)
    blocks = 2049
    model = fam.program_model(cfg, {})
    model = model.clone(cfg=dataclasses.replace(
        model.cfg, window_blocks=blocks))
    compiled = serve_program_for_v5e(program, model, 16, blocks,
                                     planes=planes)
    e, f = model.cfg.embed_dim, model.cfg.ffn_dim
    found = fused_slices(compiled, [(e, 2, f), (e, 3, e)])
    assert (found == "") == planes, found


def test_eva_tick_reads_both_pools_in_place(compiled_kernels):
    """EvaByte's tick at the cell's widths (32 heads of 128, 16 slots,
    2,049 blocks a pool; two layers), compiled for one v5e chip: the
    scanned layer holds the kernel twice, a call a pool, both pools are
    written and read in place (the output aliases all four leaves), and
    the whole-window gather's temporaries are gone."""
    import json

    from benchmark import manifest

    fam = manifest.load_family(manifest.BENCH_DIR, "evabyte")
    with open(os.path.join(REPO, "benchmark/configs/evabyte.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=2)
    slots, blocks = 16, 2049
    model = fam.program_model(cfg, {})
    model = model.clone(cfg=dataclasses.replace(
        model.cfg, window_blocks=blocks))
    compiled = serve_program_for_v5e("tick", model, slots, blocks)
    assert compiled.as_text().count(MARKER) == 2
    pool_elems = blocks * BLOCK * 4096
    assert not pool_sized_moves(compiled, pool_elems)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * 2 * pool_elems * 2   # bf16
    # the gathered tick keeps 0.5 GB of copies of windows and summaries
    assert mem.temp_size_in_bytes < 0.25e9


# ---------------------------------------------------------------------------
# nothing that hides the device


def run_py(code: str, **env):
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120, env={**os.environ, **env})


def test_launcher_and_router_parents_initialise_no_backend():
    """A process that initialises JAX on a TPU host takes every chip, so
    the parents that spawn one process per chip must not."""
    proc = run_py(
        "import pytorchdistributed_tpu, pytorchdistributed_tpu.run\n"
        "import pytorchdistributed_tpu.serving.router\n"
        "import pytorchdistributed_tpu.serving.soak\n"
        "from pytorchdistributed_tpu.runtime.launch import chip_binding\n"
        "chip_binding(4)\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n")
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_chip_binding_is_one_to_one(monkeypatch):
    from pytorchdistributed_tpu.runtime import launch

    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 4)
    envs = launch.chip_binding(4)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    assert len({e["TPU_PROCESS_ADDRESSES"] for e in envs}) == 1
    assert launch.chip_binding(1) == [{}]  # one process drives them all
    with pytest.raises(RuntimeError, match="one process drives one chip"):
        launch.chip_binding(2)
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 0)
    assert launch.chip_binding(3) == [{}, {}, {}]  # nothing to bind


def test_backend_tpu_refuses_a_cpu():
    from pytorchdistributed_tpu.config import select_backend

    with pytest.raises(RuntimeError, match="--backend tpu"):
        select_backend("tpu")
    assert os.environ["JAX_PLATFORMS"] == "cpu"  # refused before touching


def test_chip_smoke_refuses_a_cpu():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, capture_output=True,
        text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_chip_smoke_result_line_has_the_contract_keys_only():
    import importlib.util
    import json
    import types

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    line = smoke.result_line(dev, 4)
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        monkeypatch):
    from pytorchdistributed_tpu.runtime.xla_cache import (
        CACHE_DIR_ENV,
        use_persistent_cache,
    )

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    monkeypatch.setenv(CACHE_DIR_ENV, "/somewhere/else")
    assert use_persistent_cache() == "/somewhere/else"
    assert updates == []  # JAX reads the variable; no code names a directory
    monkeypatch.delenv(CACHE_DIR_ENV)
    in_checkout = os.path.join(REPO, ".jax_cache")
    assert use_persistent_cache() == in_checkout
    assert updates == [("jax_compilation_cache_dir", in_checkout)]
