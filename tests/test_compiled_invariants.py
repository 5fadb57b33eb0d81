"""Hardware-independent perf tripwires (VERDICT r4 #2).

These tests make the *compiled artifact* a guarded surface, so a round
without chip time still sees structural regressions. For each committed
config the train step is AOT-lowered from abstract state on the 8-device
CPU sim (`Trainer.lower_step` — no params materialized, nothing executed)
and its executable's invariants are asserted against committed numbers:

  * collective-op census of the optimized HLO (exact — a collective
    appearing, vanishing, or changing kind is always a deliberate event);
  * per-device flops from XLA cost analysis (exact — catches fusion /
    partitioning changes that alter the op mix);
  * arg bytes, exact: params + opt state + batch (r3's regression — BN
    buffers riding the optimizer tree — was exactly this number growing);
  * alias bytes, exact: the DONATION tripwire — if the train step's
    state donation silently breaks (jax only warns), this number drops
    and a model sized near HBM would OOM holding two state copies;
  * peak temp bytes (±2%: buffer assignment may legitimately wiggle with
    compiler-internal ordering; a real activation-footprint regression is
    far larger).

Two tiers: STRUCTURAL configs (test-size widths, every parallelism
strategy — dp / fsdp / tp x dp / 1F1B pipeline / ring / Ulysses) compile
in seconds and run in `-m quick`; FLAGSHIP configs (BASELINE.md's real
widths, depth cut to 2 layers so CPU compile stays in budget — per-layer
structure is what regresses, the committed number absorbs the depth) run
in the full suite.

When a change trips one of these ON PURPOSE (a new collective pattern, a
deliberate memory/flops tradeoff): re-capture with
`python scripts/capture_invariants.py [names...]`, update COMMITTED
below, and record the why in BASELINE.md.
"""

from __future__ import annotations

import numpy as np
import pytest

from pytorchdistributed_tpu.utils.hlo import compiled_invariants

# ---------------------------------------------------------------------------
# config builders: name -> (trainer, sample_batch)


def _lm_batch(batch, seq, vocab=128):
    rng = np.random.default_rng(0)
    return {
        "tokens": rng.integers(0, vocab, (batch, seq)).astype(np.int32),
        "targets": rng.integers(0, vocab, (batch, seq)).astype(np.int32),
    }


def _gpt2_trainer(cfg_kw, mesh_kw, strategy, *, opt=None, loss=None):
    import optax

    from pytorchdistributed_tpu.models import GPT2, gpt2_config
    from pytorchdistributed_tpu.runtime.mesh import create_mesh
    from pytorchdistributed_tpu.training import (
        Trainer,
        token_cross_entropy_loss,
    )

    return Trainer(
        GPT2(gpt2_config(**cfg_kw)), opt or optax.adamw(3e-4),
        loss or token_cross_entropy_loss,
        mesh=create_mesh(**mesh_kw), strategy=strategy, log_every=10**9)


def _structural(cfg_kw, mesh_kw, strategy):
    cfg_kw = dict(size="test", **cfg_kw)
    return lambda: (_gpt2_trainer(cfg_kw, mesh_kw, strategy),
                    _lm_batch(32, 64))


def _moe_structural():
    # Switch-MoE over the expert axis: the one strategy signature the
    # other structural configs miss (one-hot dispatch lowering to
    # all_to_all; the aux loss rides the "losses" collection)
    def build():
        from pytorchdistributed_tpu.training import (
            moe_token_cross_entropy_loss,
        )

        return (_gpt2_trainer(dict(size="test", moe_experts=4),
                              dict(data=2, expert=4), "tp",
                              loss=moe_token_cross_entropy_loss),
                _lm_batch(32, 64))

    return build


def _flagship_gpt2(size, mesh_kw=None, strategy="dp", **extra):
    # BASELINE.md's gpt2 recipe at depth 2: unrolled, no
    # remat, dense attention (the CPU stand-in for the Pallas kernels),
    # adamw, batch 8 x 1024. mesh_kw/strategy/extra let the fsdp variant
    # reuse the same recipe.
    cfg = dict(size=size, num_layers=2, attention="dense", remat=False,
               scan_layers=False)
    cfg.update(extra)
    return lambda: (_gpt2_trainer(cfg, mesh_kw or dict(data=8), strategy),
                    _lm_batch(8, 1024, vocab=50257))


def _flagship_llama():
    # BASELINE.md's llama-1b recipe at depth 2: adafactor, fused
    # chunked-CE head, dots_all remat, unrolled.
    import optax

    from pytorchdistributed_tpu.models import Llama, llama_config
    from pytorchdistributed_tpu.runtime.mesh import create_mesh
    from pytorchdistributed_tpu.training import (
        Trainer,
        fused_token_cross_entropy_loss,
    )

    def build():
        cfg = llama_config("1b", num_layers=2, max_seq_len=1024,
                           attention="dense", remat=True,
                           remat_policy="dots_all", scan_layers=False)
        tr = Trainer(Llama(cfg), optax.adafactor(3e-3),
                     fused_token_cross_entropy_loss,
                     mesh=create_mesh(data=8), strategy="dp",
                     log_every=10**9)
        return tr, _lm_batch(8, 1024, vocab=32000)

    return build


def _flagship_resnet():
    # BASELINE.md's resnet50 recipe (bf16 compute, sync-BN EMA,
    # sgd+momentum) at batch 32 instead of 256: CPU compile budget; the
    # per-image structure (conv fusions, BN stats, the single grad
    # all-reduce) is batch-size independent.
    import optax

    from pytorchdistributed_tpu.models import resnet50
    from pytorchdistributed_tpu.parallel import Policy
    from pytorchdistributed_tpu.runtime.mesh import create_mesh
    from pytorchdistributed_tpu.training import Trainer, cross_entropy_loss

    def build():
        tr = Trainer(resnet50(), optax.sgd(0.1, momentum=0.9),
                     cross_entropy_loss, mesh=create_mesh(data=8),
                     strategy="dp", precision=Policy.bf16(),
                     log_every=10**9)
        rng = np.random.default_rng(0)
        batch = {
            "image": rng.standard_normal((32, 224, 224, 3)).astype(
                np.float32),
            "label": rng.integers(0, 1000, (32,)).astype(np.int32),
        }
        return tr, batch

    return build


BUILDERS = {
    # tier 1: structural — every strategy's collective signature (quick)
    "dp8": _structural({}, dict(data=8), "dp"),
    "fsdp8": _structural({}, dict(fsdp=8), "fsdp"),
    "tp4_dp2": _structural({}, dict(data=2, tensor=4), "tp"),
    # the int8 quantized step's structural signature (ops/quant.py):
    # same dp program with the weight matmuls quantized — the int8_ops
    # census pins the convert/dot mix (5 weight-matmul sites x 2 operand
    # converts forward; int8_fwd keeps the backward in bf16, so the int
    # dot count is the forward sites only)
    "dp8_int8fwd": _structural(dict(quant="int8_fwd"), dict(data=8), "dp"),
    "tp4_dp2_int8fwd": _structural(dict(quant="int8_fwd"),
                                   dict(data=2, tensor=4), "tp"),
    # the ring collective-matmul step (ISSUE 5): same tp x dp program
    # with the QKV/out/MLP projections decomposed into ppermute rings —
    # the "overlap" census pins the ring signature (12 rings per block
    # body x (tp-1)=3 hops, on top of the partitioner's own permutes),
    # and its int8 twin pins the quantized-payload composition (the
    # gather ring ships s8 + fp32 scales)
    "tp4_dp2_ring": _structural(dict(overlap="ring"),
                                dict(data=2, tensor=4), "tp"),
    "tp4_dp2_ring_int8fwd": _structural(
        dict(overlap="ring", quant="int8_fwd"),
        dict(data=2, tensor=4), "tp"),
    "pp4_1f1b": _structural(
        dict(num_layers=4, pipeline_stages=4, pipeline_microbatches=8,
             pp_schedule="1f1b"),
        dict(data=2, pipe=4), "dp"),
    "ring_seq2": _structural(dict(attention="ring"),
                             dict(data=4, seq=2), "dp"),
    "ulysses_seq2": _structural(dict(attention="ulysses"),
                                dict(data=4, seq=2), "dp"),
    "moe_ep4": _moe_structural(),
    # tier 2: flagship widths, depth 2 (full suite)
    "gpt2s_2l": _flagship_gpt2("small"),
    "gpt2m_2l": _flagship_gpt2("medium"),
    # BASELINE config[3]'s actual recipe at depth 2: medium + ZeRO-3 +
    # activation checkpointing. The structural fsdp config is test-width,
    # where min_weight_size leaves most params replicated — only real
    # widths exercise the real shard/gather structure (the fused-CE bug
    # was invisible at test width for the same reason).
    "gpt2m_2l_fsdp8": _flagship_gpt2("medium", mesh_kw=dict(fsdp=8),
                                     strategy="fsdp", remat=True),
    # the fused 1F1B schedule at real width (the most intricate step
    # builder): 4 layers over 4 stages, 8 micro-batches, pipe x dp mesh
    "gpt2s_4l_pp4": _flagship_gpt2(
        "small", mesh_kw=dict(data=2, pipe=4), num_layers=4,
        pipeline_stages=4, pipeline_microbatches=8, pp_schedule="1f1b",
        scan_layers=True),  # the 1F1B stage decomposition requires it
    "llama1b_2l": _flagship_llama(),
    # the quantized flagship (ISSUE 1 acceptance): the gpt2 recipe
    # at depth 2 with --quant int8_fwd — per-device flops and the
    # int8 convert/dot mix are the committed tripwire for the quantized
    # train step at real widths (the int8 LM-head dot against the 50257
    # vocab dominates; a site silently falling back to bf16 changes
    # int8_ops immediately)
    "gpt2s_2l_int8fwd": _flagship_gpt2("small", quant="int8_fwd"),
    "resnet50_b32": _flagship_resnet(),
}

QUICK_NAMES = ("dp8", "fsdp8", "tp4_dp2", "dp8_int8fwd", "tp4_dp2_int8fwd",
               "tp4_dp2_ring", "tp4_dp2_ring_int8fwd",
               "pp4_1f1b", "ring_seq2", "ulysses_seq2", "moe_ep4")

# Captured by scripts/capture_invariants.py on the image's jax/XLA;
# deterministic (verified identical across cold and cache-warm compiles).
# Update ritual in the module docstring.
#
# FULL RE-CAPTURE on jax 0.9.0 / jaxlib 0.9.0 (ISSUE 21, 2026-09-26):
# the committed numbers are XLA-version-dependent BY DESIGN, and every
# entry below — the two pipeline configs included, which 0.4.x could not
# lower — was re-pinned when the image moved off 0.4.37. This XLA fuses
# the dp gradient all-reduces again (dp8 2, the flagships 1, where 0.4.x
# left 18-30) and partitions the TP/MoE einsums with far fewer
# collectives (tp4_dp2: 10 all-reduces and nothing else, where 0.4.x had
# 35 + 11 gathers + 5 permutes + 4 all-to-alls). What the numbers say,
# this capture: ring rotates KV 8 times (collective-permute 8) where
# Ulysses all-to-alls heads 8 times — the two CP dialects' signature
# difference survives the XLA version change; resnet50's 100 all-reduces
# are sync-BN's per-layer batch statistics; the *_int8fwd configs are
# the quantized-training tripwires — their int8_ops census pins the
# convert/dot mix (int8_fwd = forward sites only carry int dots, the
# backward stays bf16) and their flops sit 4-5% over the bf16 twin (the
# absmax/rescale elementwise adds — the arithmetic the MXU's 2x int8
# rate pays for).
COMMITTED: dict[str, dict] = {
    "dp8": {
        "flops": 131045120.0,
        "temp_bytes": 8681496,
        "arg_bytes": 1399816,
        "alias_bytes": 1397768,
        "collectives": {"all-reduce": 2, "all-gather": 0, "reduce-scatter": 0,
                        "collective-permute": 0, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {'all-reduce': 282372, 'all-gather': 0, 'reduce-scatter': 0, 'collective-permute': 0, 'all-to-all': 0, 'ragged-all-to-all': 0, 'collective-broadcast': 0},
    },
    "fsdp8": {
        "flops": 147790336.0,
        "temp_bytes": 14079520,
        "arg_bytes": 186184,
        "alias_bytes": 184136,
        "collectives": {"all-reduce": 11, "all-gather": 9, "reduce-scatter": 0,
                        "collective-permute": 0, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {"all-reduce": 7440132, "all-gather": 9971712,
                       "reduce-scatter": 0, "collective-permute": 0,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
    },
    "tp4_dp2": {
        "flops": 142376816.0,
        "temp_bytes": 11496920,
        "arg_bytes": 439432,
        "alias_bytes": 431240,
        "collectives": {"all-reduce": 10, "all-gather": 0, "reduce-scatter": 0,
                        "collective-permute": 0, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {"all-reduce": 1669572, "all-gather": 0,
                       "reduce-scatter": 0, "collective-permute": 0,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
    },
    # the quantized structural signatures: same programs as dp8/tp4_dp2
    # with the weight matmuls int8. Under dp the collective census must
    # NOT move (2 == 2: per-channel scales are shard-local there, so
    # quantization changes arithmetic only); under TP it legitimately
    # DOES (12 all-reduces vs 10: a contraction over a tensor-sharded dim
    # turns the absmax into a cross-shard max — ops/quant.py's sharding
    # note), which is exactly why the TP pair is pinned separately.
    # int8_ops: 5 int dots (the 5 weight-matmul sites) and 26
    # s8-producing instructions either way
    "dp8_int8fwd": {
        "flops": 136348928.0,
        "temp_bytes": 8681528,
        "arg_bytes": 1399816,
        "alias_bytes": 1397768,
        "collectives": {"all-reduce": 2, "all-gather": 0, "reduce-scatter": 0,
                        "collective-permute": 0, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 26, "int_dots": 5},
        "comm_bytes": {'all-reduce': 282372, 'all-gather': 0, 'reduce-scatter': 0, 'collective-permute': 0, 'all-to-all': 0, 'ragged-all-to-all': 0, 'collective-broadcast': 0},
    },
    "tp4_dp2_int8fwd": {
        "flops": 149612816.0,
        "temp_bytes": 11497080,
        "arg_bytes": 439432,
        "alias_bytes": 431240,
        "collectives": {"all-reduce": 12, "all-gather": 0, "reduce-scatter": 0,
                        "collective-permute": 0, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 26, "int_dots": 5},
        "comm_bytes": {"all-reduce": 1678276, "all-gather": 0,
                       "reduce-scatter": 0, "collective-permute": 0,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
    },
    # the ring collective-matmul signatures (ISSUE 5), re-captured on
    # jax 0.9.0. What the numbers say: every collective-permute is a
    # ring hop now (the monolithic tp4_dp2 has none on this XLA): 30 =
    # 10 x (tp-1)=3 hops out of the 4 projection sites (qkv/out/wi/wo) x
    # 3 rings each (fwd, bwd-dx, bwd-dw) in the one scanned block body —
    # why 10 rotations and not 12 survive in the optimized HLO has not
    # been looked into. The int8 twin adds 6 permutes (the two column fwd
    # rings ship a second array — the fp32 row scales next to the s8
    # payload) yet its ppermute BYTES drop 1966080 → 1677312: the int8
    # payload is a quarter the fp32 chunk, the ISSUE's comm-bytes÷4
    # claim in census form. int8_ops 60/17 > the monolithic tp twin's
    # 26/5: every ring chunk is its own int8 dot (12 int dots across the
    # 4 sites' rings + the LM-head/CE sites), the per-chunk scales are
    # extra s8-producing converts. flops sit ~11% over tp4_dp2 — the
    # fp32 ring accumulators and dynamic-update-slices the cost model
    # bills; the MXU-rate win this buys is a hardware question for a
    # chip A/B, not the sim.
    "tp4_dp2_ring": {
        "flops": 157467952.0,
        "temp_bytes": 8433496,
        "arg_bytes": 439432,
        "alias_bytes": 431240,
        "collectives": {"all-reduce": 11, "all-gather": 2, "reduce-scatter": 0,
                        "collective-permute": 30, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {"all-reduce": 1081028, "all-gather": 524288,
                       "reduce-scatter": 0, "collective-permute": 1966080,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
        "overlap": {"async_pairs": {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
                    "collective-permute": 0, "all-to-all": 0,
                    "ragged-all-to-all": 0, "collective-broadcast": 0}, "unpaired_starts": 0, "overlapped_ops": 0, "ppermute": 30},
    },
    "tp4_dp2_ring_int8fwd": {
        "flops": 164880816.0,
        "temp_bytes": 8433648,
        "arg_bytes": 439432,
        "alias_bytes": 431240,
        "collectives": {"all-reduce": 11, "all-gather": 2, "reduce-scatter": 0,
                        "collective-permute": 36, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 60, "int_dots": 17},
        "comm_bytes": {"all-reduce": 1081028, "all-gather": 524288,
                       "reduce-scatter": 0, "collective-permute": 1677312,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
        "overlap": {"async_pairs": {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
                    "collective-permute": 0, "all-to-all": 0,
                    "ragged-all-to-all": 0, "collective-broadcast": 0}, "unpaired_starts": 0, "overlapped_ops": 0, "ppermute": 36},
    },
    # the 1F1B schedule's partial-manual shard_map over "pipe": first
    # capture since r5 (0.4.x could not lower it)
    "pp4_1f1b": {
        "flops": 89115424.0,
        "temp_bytes": 2992960,
        "arg_bytes": 806152,
        "alias_bytes": 797960,
        "collectives": {"all-reduce": 3, "all-gather": 0,
                        "reduce-scatter": 0, "collective-permute": 2,
                        "all-to-all": 3, "ragged-all-to-all": 0,
                        "collective-broadcast": 0},
    },
    "ring_seq2": {
        "flops": 118030232.0,
        "temp_bytes": 7425056,
        "arg_bytes": 1399816,
        "alias_bytes": 1397768,
        "collectives": {"all-reduce": 5, "all-gather": 3, "reduce-scatter": 0,
                        "collective-permute": 8, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {"all-reduce": 736776, "all-gather": 98304,
                       "reduce-scatter": 0, "collective-permute": 409600,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
    },
    "ulysses_seq2": {
        "flops": 120004488.0,
        "temp_bytes": 7310272,
        "arg_bytes": 1399816,
        "alias_bytes": 1397768,
        "collectives": {"all-reduce": 5, "all-gather": 3, "reduce-scatter": 0,
                        "collective-permute": 2, "all-to-all": 8,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {"all-reduce": 736776, "all-gather": 98304,
                       "reduce-scatter": 0, "collective-permute": 16384,
                       "all-to-all": 524288, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
    },
    # ISSUE 14 recapture: the explicit a2a dispatch (ops/overlap.
    # expert_a2a_ffn) replaced the auto-partitioned one-hot einsums,
    # which XLA used to lower as all-gather + all-reduce with a GLOBAL
    # capacity buffer. Grouped per-shard capacity cut per-device flops
    # 852M -> 198M and temp bytes 45.7M -> 8.4M, and the 4 all-to-alls
    # in the scanned layer body are exactly the contract: dispatch +
    # combine forward, and both exchange directions again in backward.
    "moe_ep4": {
        "flops": 240953696.0,
        "temp_bytes": 9207424,
        "arg_bytes": 1399816,
        "alias_bytes": 1391624,
        "collectives": {"all-reduce": 6, "all-gather": 11, "reduce-scatter": 0,
                        "collective-permute": 0, "all-to-all": 4,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {'s8_values': 0, 'int_dots': 0},
        "comm_bytes": {"all-reduce": 675624, "all-gather": 2131968,
                       "reduce-scatter": 0, "collective-permute": 0,
                       "all-to-all": 327680, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
        "a2a": {'count': 4, 'bytes': 327680},
    },
    "gpt2s_2l": {
        "flops": 348919955456.0,
        "temp_bytes": 1316690288,
        "arg_bytes": 642741256,
        "alias_bytes": 642733064,
        "collectives": {"all-reduce": 1, "all-gather": 0, "reduce-scatter": 0,
                        "collective-permute": 0, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {'all-reduce': 368633860, 'all-gather': 0, 'reduce-scatter': 0, 'collective-permute': 0, 'all-to-all': 0, 'ragged-all-to-all': 0, 'collective-broadcast': 0},
    },
    "gpt2m_2l": {
        "flops": 503792271360.0,
        "temp_bytes": 1587454320,
        "arg_bytes": 932483080,
        "alias_bytes": 932474888,
        "collectives": {"all-reduce": 1, "all-gather": 0, "reduce-scatter": 0,
                        "collective-permute": 0, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {'all-reduce': 516677636, 'all-gather': 0, 'reduce-scatter': 0, 'collective-permute': 0, 'all-to-all': 0, 'ragged-all-to-all': 0, 'collective-broadcast': 0},
    },
    # Census caveat, verified with a minimal probe in r5:
    # XLA:CPU lowers the canonical grad reduce-scatter pattern as
    # all-reduce + slice — fsdp rows legitimately show reduce-scatter 0
    # here; on TPU the same programs get the ReduceScatterCreator pass.
    # The CPU census is still a valid tripwire, just not a bandwidth
    # model of the TPU lowering.
    "gpt2m_2l_fsdp8": {
        "flops": 513154646016.0,
        "temp_bytes": 5980155704,
        "arg_bytes": 116718088,
        "alias_bytes": 116709896,
        "collectives": {"all-reduce": 19, "all-gather": 15, "reduce-scatter":
                        0, "collective-permute": 0, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {"all-reduce": 2453102596, "all-gather": 2787713024,
                       "reduce-scatter": 0, "collective-permute": 0,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
    },
    # first capture since r5 (see pp4_1f1b)
    "gpt2s_4l_pp4": {
        "flops": 309091106816.0,
        "temp_bytes": 1861801464,
        "arg_bytes": 557711368,
        "alias_bytes": 557678600,
        "collectives": {"all-reduce": 27, "all-gather": 2,
                        "reduce-scatter": 0, "collective-permute": 2,
                        "all-to-all": 0, "ragged-all-to-all": 0,
                        "collective-broadcast": 0},
    },
    # (the r5 fused-CE seq-chunking fix's zero-all-gather property —
    # BASELINE.md "First catch" — still holds under this XLA: no
    # all-gathers in the pure-DP llama program)
    "llama1b_2l": {
        "flops": 947261276160.0,
        "temp_bytes": 2622011976,
        "arg_bytes": 1011542024,
        "alias_bytes": 1011533832,
        "collectives": {"all-reduce": 2, "all-gather": 0, "reduce-scatter": 0,
                        "collective-permute": 0, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {'all-reduce': 1010868228, 'all-gather': 0, 'reduce-scatter': 0, 'collective-permute': 0, 'all-to-all': 0, 'ragged-all-to-all': 0, 'collective-broadcast': 0},
    },
    # the quantized flagship (ISSUE 1 acceptance): 18 converts / 9 int
    # dots = 2 unrolled layers x 4 weight-matmul sites + the tied LM
    # head; flops +0.4% over gpt2s_2l (absmax/rescale elementwise), temp
    # -4% (int8 operand buffers are a quarter the bf16 footprint)
    "gpt2s_2l_int8fwd": {
        "flops": 350589911040.0,
        "temp_bytes": 1316737392,
        "arg_bytes": 642741256,
        "alias_bytes": 642733064,
        "collectives": {"all-reduce": 1, "all-gather": 0, "reduce-scatter": 0,
                        "collective-permute": 0, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 47, "int_dots": 9},
        "comm_bytes": {'all-reduce': 368633860, 'all-gather': 0, 'reduce-scatter': 0, 'collective-permute': 0, 'all-to-all': 0, 'ragged-all-to-all': 0, 'collective-broadcast': 0},
    },
    "resnet50_b32": {
        "flops": 105789972480.0,
        "temp_bytes": 499951336,
        "arg_bytes": 207077204,
        "alias_bytes": 204668740,
        "collectives": {"all-reduce": 100, "all-gather": 0, "reduce-scatter":
                        0, "collective-permute": 0, "all-to-all": 0,
                        "ragged-all-to-all": 0, "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {'all-reduce': 102653096, 'all-gather': 0, 'reduce-scatter': 0, 'collective-permute': 0, 'all-to-all': 0, 'ragged-all-to-all': 0, 'collective-broadcast': 0},
    },
}

TEMP_BYTES_RTOL = 0.02


def _assert_invariants(name, inv, want):
    assert inv["collectives"] == want["collectives"], (
        f"{name}: collective census changed — deliberate? "
        f"got {inv['collectives']}, committed {want['collectives']}")
    assert inv["flops"] == want["flops"], (
        f"{name}: per-device flops changed: got {inv['flops']:.6g}, "
        f"committed {want['flops']:.6g}")
    assert inv["arg_bytes"] == want["arg_bytes"], (
        f"{name}: params+opt_state+batch bytes changed: got "
        f"{inv['arg_bytes']}, committed {want['arg_bytes']} (state bloat? "
        f"r3's BN-in-opt-tree bug was this number growing)")
    assert inv["alias_bytes"] == want["alias_bytes"], (
        f"{name}: donated/aliased bytes changed: got "
        f"{inv['alias_bytes']}, committed {want['alias_bytes']} — if it "
        f"DROPPED, state donation broke (jax only warns) and the step now "
        f"holds two copies of params+opt state")
    if "int8_ops" in want:
        assert inv["int8_ops"] == want["int8_ops"], (
            f"{name}: int8 convert/dot mix changed: got {inv['int8_ops']}, "
            f"committed {want['int8_ops']} — a quantized site silently "
            f"falling back to bf16 (or an int8 op leaking into a bf16 "
            f"config) shows up exactly here")
    if "overlap" in want:
        assert inv["overlap"] == want["overlap"], (
            f"{name}: overlap census changed: got {inv['overlap']}, "
            f"committed {want['overlap']} — the ppermute ring count / "
            f"async pairing signature of the latency-hiding path (a ring "
            f"site silently falling back to the monolithic collective, "
            f"or a hop appearing/vanishing, shows up exactly here)")
    if "comm_bytes" in want:
        assert inv["comm_bytes"] == want["comm_bytes"], (
            f"{name}: per-device collective bytes changed: got "
            f"{inv['comm_bytes']}, committed {want['comm_bytes']} — the "
            f"comm-volume half of the census, and a StepAccounting input: "
            f"either communication volume really moved (deliberate?) or "
            f"the telemetry comm-bytes/MFU math would now misreport")
    if "a2a" in want:
        assert inv["a2a"] == want["a2a"], (
            f"{name}: all-to-all census changed: got {inv['a2a']}, "
            f"committed {want['a2a']} — the expert-parallel MoE "
            f"dispatch/combine signature (2 fwd + 2 bwd per MoE layer "
            f"from ops/overlap.expert_a2a_ffn): the explicit exchange "
            f"either stopped lowering to a literal all_to_all or a pass "
            f"duplicated/split one, and the payload bytes pin the int8 "
            f"vs fp32 wire format")
    lo = want["temp_bytes"] * (1 - TEMP_BYTES_RTOL)
    hi = want["temp_bytes"] * (1 + TEMP_BYTES_RTOL)
    assert lo <= inv["temp_bytes"] <= hi, (
        f"{name}: peak temp memory moved >{TEMP_BYTES_RTOL:.0%}: got "
        f"{inv['temp_bytes']}, committed {want['temp_bytes']}")


def _check(name):
    trainer, batch = BUILDERS[name]()
    inv = compiled_invariants(trainer.lower_step(batch).compile())
    _assert_invariants(name, inv, COMMITTED[name])


@pytest.mark.parametrize("name", QUICK_NAMES)
def test_structural_invariants(name):
    _check(name)


@pytest.mark.parametrize(
    "name", [n for n in BUILDERS if n not in QUICK_NAMES])
def test_flagship_invariants(name):
    _check(name)


def test_diag_off_hlo_byte_identical(monkeypatch):
    """ISSUE 6 acceptance, wired into the capture_invariants flow: with
    diagnostics DISABLED, the compiled train step must be byte-identical
    to the pre-knob program — not "equal invariants", the same HLO text
    to the byte (the committed numeric pins above bound drift vs the
    pre-PR captures; this bounds the off-path's contribution to exactly
    zero). Covers all three off spellings (default, explicit "off",
    env "off") and sanity-checks that turning diagnostics ON does change
    the program — a knob whose on-path is invisible would mean the sow
    sites silently stopped collecting."""
    import optax

    from pytorchdistributed_tpu.models import GPT2, gpt2_config
    from pytorchdistributed_tpu.runtime.mesh import create_mesh
    from pytorchdistributed_tpu.training import (
        Trainer,
        token_cross_entropy_loss,
    )
    from pytorchdistributed_tpu.utils.hlo import hlo_fingerprint

    batch = _lm_batch(32, 64)

    def fingerprint(diagnostics):
        tr = Trainer(GPT2(gpt2_config("test")), optax.adamw(3e-4),
                     token_cross_entropy_loss, mesh=create_mesh(data=8),
                     strategy="dp", log_every=10**9,
                     diagnostics=diagnostics)
        return hlo_fingerprint(tr.lower_step(batch).compile())

    monkeypatch.delenv("PTD_DIAGNOSTICS", raising=False)
    base = fingerprint(None)
    assert fingerprint("off") == base, (
        "Trainer(diagnostics='off') compiled a DIFFERENT program than the "
        "default — the off path must add nothing")
    monkeypatch.setenv("PTD_DIAGNOSTICS", "scalars")
    assert fingerprint("off") == base, (
        "explicit diagnostics='off' must beat the PTD_DIAGNOSTICS env")
    assert fingerprint(None) != base, (
        "PTD_DIAGNOSTICS=scalars left the program unchanged — the "
        "diagnostics sow/step sites are not collecting")


DECODE_COMMITTED: dict = {
    "flops": 226508308480.0,
    "temp_bytes": 811830472,
    "arg_bytes": 214252552,
    "alias_bytes": 0,  # generate() does not donate — no state to reuse
    "collectives": {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
                    "collective-permute": 0, "all-to-all": 0,
                    "ragged-all-to-all": 0, "collective-broadcast": 0},
    "int8_ops": {"s8_values": 0, "int_dots": 0},
    "comm_bytes": {'all-reduce': 0, 'all-gather': 0, 'reduce-scatter': 0, 'collective-permute': 0, 'all-to-all': 0, 'ragged-all-to-all': 0, 'collective-broadcast': 0},
}


def decode_lowered():
    """Lower the full generate() program — chunked prefill + 128-tick
    lax.scan with KV cache, BASELINE.md's decode shape at depth 2.
    Shared by test_decode_invariants and scripts/capture_invariants.py
    (the recapture ritual covers "decode" by name)."""
    import dataclasses

    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from pytorchdistributed_tpu.inference import generate_jit
    from pytorchdistributed_tpu.models import GPT2, gpt2_config

    cfg = gpt2_config("small", num_layers=2, scan_layers=False)
    model = GPT2(cfg)
    boxed = jax.eval_shape(model.init, jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))
    params_sds = nn.meta.unbox(boxed)
    dm = GPT2(dataclasses.replace(cfg, decode=True))
    prompt_sds = jax.ShapeDtypeStruct((4, 512), jnp.int32)
    # the prng key is concrete (tiny); params/prompt stay abstract.
    # generate_jit, not generate: the public name is now a thin wrapper
    # (stop-id normalization + eager validation) around this jit.
    return generate_jit.lower(dm, params_sds, prompt_sds,
                              max_new_tokens=128, temperature=0.8,
                              top_k=40, rng=jax.random.key(1))


def test_decode_invariants():
    """The one-shot decode path's tripwire: the committed decode headline
    (BASELINE.md's gpt2s_decode_tokens_per_s) had no hardware-independent
    guard. Decode is single-chip, so the collective census should stay
    all-zero; temp bytes bound the KV-cache + scan working set."""
    inv = compiled_invariants(decode_lowered().compile())
    _assert_invariants("decode", inv, DECODE_COMMITTED)


# ---------------------------------------------------------------------------
# serving-engine pins (ISSUE 3): the two compiled programs steady-state
# serving dispatches — the slot decode tick and the prefill-into-slot —
# at structural (test) width, 4 slots, the committed candidates=64
# sampler. Collectives must stay all-zero (single-chip serving; an
# accidental collective in the tick would tank per-token latency), the
# int8 census pins the --quant composition (5 weight-matmul sites x 2
# operand converts forward; prefill adds nothing — same sites), and temp
# bytes bound the tick's working set next to the [slots, S, kv, hd]
# donated cache.

SERVING_NAMES = ("serve_tick", "serve_prefill", "serve_tick_int8fwd",
                 "serve_prefill_int8fwd", "serve_tick_paged",
                 "serve_prefill_paged", "serve_spec_tick")


def serving_lowered(name: str):
    """Lower one serving program by pin name (shared with
    scripts/capture_invariants.py — the recapture ritual covers the
    SERVING_NAMES). The ``*_paged`` pair (ISSUE 7) lowers the paged
    engine's steady-state programs — the pool-donated block-table tick
    and the chunked prefill — at block 16 over a same-HBM pool;
    ``serve_spec_tick`` (ISSUE 8) lowers the speculative draft-and-
    verify tick (self-drafted, spec_k=4) over the same pool geometry —
    BOTH pools donated, zero collectives."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from pytorchdistributed_tpu.models import GPT2, gpt2_config
    from pytorchdistributed_tpu.serving.engine import (
        decode_tick,
        paged_decode_tick,
        paged_prefill_chunk,
        paged_slot_models,
        prefill_into_slot,
        slot_models,
        spec_decode_tick,
    )

    slots, candidates, bucket = 4, 64, 128
    quant = "int8_fwd" if name.endswith("_int8fwd") else "none"
    model = GPT2(gpt2_config("test", quant=quant))
    paged = name.endswith("_paged") or name == "serve_spec_tick"
    if paged:
        block, pages = 16, model.cfg.max_seq_len // 16
        tick_model, chunk_model = paged_slot_models(
            model, slots, block, slots * pages + 1)
    else:
        tick_model, prefill_model = slot_models(model, slots)
    boxed = jax.eval_shape(model.init, jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))
    weights_sds = nn.meta.unbox(boxed)["params"]
    cache_sds = jax.eval_shape(lambda: tick_model.init(
        jax.random.key(0), jnp.zeros((slots, 1), jnp.int32))["cache"])
    kd = jax.random.key_data(jax.random.key(0))
    i32, f32 = jnp.int32, jnp.float32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    if name == "serve_prefill_paged":
        return paged_prefill_chunk.lower(
            chunk_model, weights_sds, cache_sds,
            sds((1, bucket), i32),                       # prompt chunk
            sds((), i32),                                # start
            {"block_table":
             sds((tick_model.cfg.kv_pages,), i32)},      # table row
            sds((), i32),                                # true_len
            sds(kd.shape, kd.dtype), sds((), i32),       # key, count
            sds((), f32), sds((), i32), sds((), f32),    # sampling params
            candidates=candidates)
    if name == "serve_spec_tick":
        # self-drafted: draft model/weights/cache mirror the target's —
        # the pin still covers the two-pool donation + the fused
        # rollout/verify/accept program shape
        return spec_decode_tick.lower(
            tick_model, tick_model, weights_sds, weights_sds,
            cache_sds, cache_sds,
            {"block_table":                              # block tables
             sds((slots, tick_model.cfg.kv_pages), i32)},
            sds((slots,), i32),                          # lengths
            sds((slots,), i32),                          # tokens
            sds((slots,) + kd.shape, kd.dtype), sds((slots,), i32),
            sds((slots,), f32), sds((slots,), i32), sds((slots,), f32),
            spec_k=4, candidates=candidates)
    if name == "serve_tick_paged":
        return paged_decode_tick.lower(
            tick_model, weights_sds, cache_sds,
            {"block_table":                              # block tables
             sds((slots, tick_model.cfg.kv_pages), i32)},
            sds((slots,), i32),                          # lengths
            sds((slots,), i32),
            sds((slots,) + kd.shape, kd.dtype), sds((slots,), i32),
            sds((slots,), f32), sds((slots,), i32), sds((slots,), f32),
            candidates=candidates)
    if name.startswith("serve_prefill"):
        return prefill_into_slot.lower(
            prefill_model, weights_sds, cache_sds,
            sds((1, bucket), i32),                       # bucketed prompt
            sds((), i32), sds((), i32),                  # true_len, slot
            sds(kd.shape, kd.dtype), sds((), i32),       # key, count
            sds((), f32), sds((), i32), sds((), f32),    # sampling params
            candidates=candidates)
    return decode_tick.lower(
        tick_model, weights_sds, cache_sds, sds((slots,), i32),
        sds((slots,) + kd.shape, kd.dtype), sds((slots,), i32),
        sds((slots,), f32), sds((slots,), i32), sds((slots,), f32),
        candidates=candidates)


# Re-captured 2026-09-26 on jax 0.9.0 (scripts/capture_invariants.py
# with the serving names). What the numbers say: alias_bytes 262192 on every
# entry IS the donated slot cache ([4, 128, 4, 16] K+V bf16 x 2 layers +
# the position counters) — if donation breaks, steady-state serving
# holds two cache copies and this drops to 0; the int8 rows carry the
# same 26 s8-values / 5-int-dot mix as dp8_int8fwd (identical weight-
# matmul sites, the sampler adds none).
SERVE_COMMITTED: dict[str, dict] = {
    "serve_tick": {
        "flops": 1618876.0,
        "temp_bytes": 1102560,
        "arg_bytes": 728224,
        "alias_bytes": 262192,
        "collectives": {"all-reduce": 0, "all-gather": 0,
                        "reduce-scatter": 0, "collective-permute": 0,
                        "all-to-all": 0, "ragged-all-to-all": 0,
                        "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {"all-reduce": 0, "all-gather": 0,
                       "reduce-scatter": 0, "collective-permute": 0,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
    },
    # serve_prefill*: the resume-from-tokens count argument (ISSUE 9)
    # is in the prefill signature — 4 arg_bytes (one i32 scalar) and a
    # fold_in that reads a dynamic count instead of a folded constant.
    "serve_prefill": {
        "flops": 22194940.0,
        "temp_bytes": 829064,
        "arg_bytes": 728656,
        "alias_bytes": 262192,
        "collectives": {"all-reduce": 0, "all-gather": 0,
                        "reduce-scatter": 0, "collective-permute": 0,
                        "all-to-all": 0, "ragged-all-to-all": 0,
                        "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {"all-reduce": 0, "all-gather": 0,
                       "reduce-scatter": 0, "collective-permute": 0,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
    },
    "serve_tick_int8fwd": {
        "flops": 2111364.0,
        "temp_bytes": 997280,
        "arg_bytes": 728224,
        "alias_bytes": 262192,
        "collectives": {"all-reduce": 0, "all-gather": 0,
                        "reduce-scatter": 0, "collective-permute": 0,
                        "all-to-all": 0, "ragged-all-to-all": 0,
                        "collective-broadcast": 0},
        "int8_ops": {"s8_values": 26, "int_dots": 5},
        "comm_bytes": {"all-reduce": 0, "all-gather": 0,
                       "reduce-scatter": 0, "collective-permute": 0,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
    },
    "serve_prefill_int8fwd": {
        "flops": 23737788.0,
        "temp_bytes": 696712,
        "arg_bytes": 728656,
        "alias_bytes": 262192,
        "collectives": {"all-reduce": 0, "all-gather": 0,
                        "reduce-scatter": 0, "collective-permute": 0,
                        "all-to-all": 0, "ragged-all-to-all": 0,
                        "collective-broadcast": 0},
        "int8_ops": {"s8_values": 26, "int_dots": 5},
        "comm_bytes": {"all-reduce": 0, "all-gather": 0,
                       "reduce-scatter": 0, "collective-permute": 0,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
    },
    # Paged engine (ISSUE 7):
    # alias_bytes 270336 on the tick IS the donated block POOL
    # ([2 layers x 33 blocks x 16 x 64 lanes] K+V bf16 = 270336 — the
    # same-HBM pool at 4 slots x 8 pages + trash) — if it drops,
    # donation broke and every tick copies the whole pool; the prefill
    # chunk additionally aliases the counter/table scratch (270496).
    # Re-captured 2026-10-01 (PR 28: the pool lane-dense and carried
    # through the layer loop — fewer flops and temp bytes because the
    # per-layer slice/update-slice of the stacked pool is gone; the
    # chunk passes and aliases 144 bytes less: one position vector and
    # one block table a stack, not one a layer).
    # Zero collectives: paging is single-chip address arithmetic, a
    # gather/scatter that partitions — an accidental collective in the
    # tick is a per-token latency bug.
    "serve_tick_paged": {
        "flops": 1503924.0,
        "temp_bytes": 857600,
        "arg_bytes": 736512,
        "alias_bytes": 270336,
        "collectives": {"all-reduce": 0, "all-gather": 0,
                        "reduce-scatter": 0, "collective-permute": 0,
                        "all-to-all": 0, "ragged-all-to-all": 0,
                        "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {"all-reduce": 0, "all-gather": 0,
                       "reduce-scatter": 0, "collective-permute": 0,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
    },
    # Speculative tick (ISSUE 8):
    # alias_bytes 540672 == 2 x 270336 — BOTH donated pools (target +
    # self-draft twin); if it halves, one cache stopped aliasing and
    # every spec tick copies a whole pool. flops ~3.6x the plain paged
    # tick (5 draft rollout steps + the k+1-wide verify vs one s=1
    # apply) for up to spec_k+1=5 tokens emitted. Zero collectives:
    # draft rollout, verify and the rejection kernel are all
    # single-chip; a collective here is a per-token latency bug.
    "serve_spec_tick": {
        "flops": 5722313.0,
        "temp_bytes": 979696,
        "arg_bytes": 1472768,
        "alias_bytes": 540672,
        "collectives": {"all-reduce": 0, "all-gather": 0,
                        "reduce-scatter": 0, "collective-permute": 0,
                        "all-to-all": 0, "ragged-all-to-all": 0,
                        "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {"all-reduce": 0, "all-gather": 0,
                       "reduce-scatter": 0, "collective-permute": 0,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
    },
    "serve_prefill_paged": {
        "flops": 22134916.0,
        "temp_bytes": 808768,
        "arg_bytes": 736992,
        "alias_bytes": 270496,
        "collectives": {"all-reduce": 0, "all-gather": 0,
                        "reduce-scatter": 0, "collective-permute": 0,
                        "all-to-all": 0, "ragged-all-to-all": 0,
                        "collective-broadcast": 0},
        "int8_ops": {"s8_values": 0, "int_dots": 0},
        "comm_bytes": {"all-reduce": 0, "all-gather": 0,
                       "reduce-scatter": 0, "collective-permute": 0,
                       "all-to-all": 0, "ragged-all-to-all": 0,
                       "collective-broadcast": 0},
    },
}


@pytest.mark.parametrize("name", SERVING_NAMES)
def test_serving_invariants(name):
    inv = compiled_invariants(serving_lowered(name).compile())
    _assert_invariants(name, inv, SERVE_COMMITTED[name])


# comm_stall_frac (telemetry/accounting.py, ISSUE 5c) computed from the
# compiled artifact alone — comm bytes at the nominal ICI table over
# comm + compute at nominal peaks, cpu-sim-nominal denominators on this
# rig — so the estimator itself is pinnable: a change to the ICI table,
# the byte census, or the stall formula moves these numbers.
# Re-captured 2026-09-26 on jax 0.9.0. On 0.4.x the ring config billed
# fewer bytes than its monolithic twin (0.683 < 0.7473); this XLA
# partitions the monolithic program with all-reduces only (no gathered
# buffers to bill), so the order is reversed (0.694 > 0.5397) — an
# estimate from the CPU partitioner's census, not a chip measurement.
STALL_COMMITTED = {
    "dp8": 0.1773,
    "fsdp8": 0.9218,
    "tp4_dp2": 0.5397,
    "tp4_dp2_ring": 0.694,
}


@pytest.mark.parametrize("name", sorted(STALL_COMMITTED))
def test_comm_stall_frac_pinned(name):
    """The structural comm-stall estimator, end to end: lower the step,
    build StepAccounting from the executable, assert the zero-overlap
    stall fraction against the committed value. Also pins the measured
    variant's arithmetic (a fixed fake step time) so both denominators
    of comm_stall_frac are covered."""
    from pytorchdistributed_tpu.telemetry import StepAccounting

    trainer, batch = BUILDERS[name]()
    acct = StepAccounting.from_compiled(
        trainer.lower_step(batch).compile(), batch=batch,
        n_devices=trainer.mesh.devices.size)
    assert acct.ici_source == "cpu-sim-nominal"
    assert acct.comm_stall_frac() == STALL_COMMITTED[name]
    # measured-denominator variant: bytes / ici / sec, capped at 1
    sec = 0.010
    want = round(min(1.0, acct.comm_bytes_per_step
                     / acct.ici_bytes_per_s / sec), 4)
    assert acct.comm_stall_frac(sec) == want
    assert acct.comm_stall_frac(0.0) is None


# ---------------------------------------------------------------------------
# the served layout: a scanned stack's fused kernels as planes


def _first_uses(jaxpr, var):
    """Every equation that uses `var` in `jaxpr` with the operand's
    index, followed into the bodies of the calls and loops that hand it
    on whole (a scan hands its body the step's slice of it)."""
    from pytorchdistributed_tpu.serving.weights import _bodies

    for eqn in jaxpr.eqns:
        for i, v in enumerate(eqn.invars):
            if v is not var:
                continue
            inner = [body for body in _bodies(eqn)
                     if len(body.invars) == len(eqn.invars)]
            if inner:
                for body in inner:
                    yield from _first_uses(body, body.invars[i])
            else:
                yield eqn, i


def test_served_tick_reads_plane_leaves_straight_into_their_products():
    """The scanned toy Llama's jitted tick takes its fused k/v and gate/up
    as planes, `[layers, 2, embed, width]`, and every use of such a leaf,
    through the layer loop, is the right operand of a `dot_general` that
    contracts the embedding: nothing but the scan's slice stands between
    the two, no transpose, reshape or convert. (XLA then reads a layer's
    planes where they lie: tests/test_tpu_lowering.py.)"""
    import jax
    import jax.numpy as jnp

    from pytorchdistributed_tpu.models import Llama, llama_config
    from pytorchdistributed_tpu.serving import ServingEngine

    model = Llama(llama_config("test", max_seq_len=64))
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    engine = ServingEngine(model, params, num_slots=2, block_size=8,
                           prefill_chunk=8, prefix_cache=False)
    tick, args = engine._tick_program()
    closed = tick.trace(engine._tick_model, *args,
                        candidates=engine.candidates).jaxpr
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(args[0])]
    planes = {i: path for i, path in enumerate(paths) if "_planes" in path}
    assert sorted(p.split("'")[-2] for p in planes.values()) == [
        "kv_planes", "wi_planes"]
    assert not any(p.endswith(("['kv_kernel']", "['wi_kernel']"))
                   for p in paths)
    cfg = model.cfg
    for i, path in planes.items():
        leaf = closed.jaxpr.invars[i]
        assert leaf.aval.shape[:3] == (cfg.num_layers, 2, cfg.embed_dim)
        uses = list(_first_uses(closed.jaxpr, leaf))
        assert uses, path
        for eqn, operand in uses:
            assert (eqn.primitive.name, operand) == ("dot_general", 1), (
                path, eqn)
            (_, rhs_contract), _ = eqn.params["dimension_numbers"]
            assert tuple(rhs_contract) == (1,), (path, eqn)
            assert eqn.invars[1].aval.shape == leaf.aval.shape[1:]
    engine.close()


def test_train_step_is_unchanged_by_the_served_layout():
    """Serving re-lays the engine's own copy of a tree; the `Trainer`
    keeps the checkpoint's fused q/k/v `[layers, embed, 3, width]`, and
    its compiled step is the same program, to the byte, before and after
    an engine serves the same model."""
    import jax
    import jax.numpy as jnp

    from pytorchdistributed_tpu.models import GPT2, gpt2_config
    from pytorchdistributed_tpu.serving import ServingEngine
    from pytorchdistributed_tpu.utils.hlo import hlo_fingerprint

    trainer, batch = BUILDERS["dp8"]()
    before = hlo_fingerprint(trainer.lower_step(batch).compile())
    state = trainer.init(batch)
    cfg = gpt2_config("test")
    attn = state.params["params"]["h"]["block"]["attn"]
    qkv = attn["qkv_kernel"]
    assert qkv.shape == (cfg.num_layers, cfg.embed_dim, 3, cfg.embed_dim)
    model = GPT2(cfg)
    engine = ServingEngine(model, model.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)), num_slots=2,
        block_size=8, prefill_chunk=8, prefix_cache=False)
    assert engine.summary()["weight_bytes_relaid"] > 0
    engine.submit(np.arange(5, dtype=np.int32), max_new_tokens=3)
    engine.run_until_idle()
    engine.close()
    assert hlo_fingerprint(trainer.lower_step(batch).compile()) == before
    assert "qkv_planes" not in attn
