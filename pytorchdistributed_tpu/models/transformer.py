"""Shared Transformer core for the model zoo (GPT-2, BERT, ViT).

The reference never ships a transformer (its LLaMA demo,
03_model_parallel.ipynb:86, failed to run), but the BASELINE configs demand
BERT-base MLM, GPT-2-medium FSDP and ViT-L/16 — so one TPU-first core serves
all three. Design decisions (SURVEY.md §7 stance — strategies are sharding
choices, not model rewrites):

  * every parameter carries *logical* axis names via
    `nn.with_logical_partitioning`; parallel/tp.py's rule tables map them to
    mesh axes, so DDP/FSDP/TP/2D reuse this exact module;
  * layers can be stacked with `nn.scan` (one compiled block body instead of
    N inlined copies — faster XLA compiles, and the scanned "stage" axis is
    what pipeline parallelism shards);
  * `remat` wraps the block in `jax.checkpoint` (GPipe's activation
    recomputation, reference 03_model_parallel.ipynb:637-643);
  * attention backend is pluggable: "dense" | "pallas" (flash kernel) |
    "ring" (context parallel over the seq axis) | "ulysses" (all-to-all);
  * compute dtype bf16-by-default for the MXU; LayerNorm/softmax accumulate
    fp32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import flax.linen as nn
import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from pytorchdistributed_tpu.ops.attention import (
    dense_attention,
    paged_gather,
)
from pytorchdistributed_tpu.parallel.tp import Logical

Dtype = Any


class CacheKind(NamedTuple):
    """One kind of cache a paged model keeps, as the serving engine's
    `SlotPool` takes it (`cfg.cache_kinds`, the stream's own first). A
    kind without a table is a recurrent state (`TransformerConfig.
    state_leaves`): one fixed state a slot, overwritten in place every
    call, with no rows and no blocks to page."""
    kind: str | None        # the `pool` id on the engine's spans
    # the "cache" leaf the model reads block ids from; None: no blocks
    table: str | None
    # positions a query of this pool's layers sees, itself included; 0:
    # every position (the pool grows with the stream and never retires)
    window: int = 0
    # positions one row of the pool stands for (a summary row a chunk)
    stride: int = 1
    # how a windowed pool retires: False slides (a block goes once the
    # window has passed it), True tumbles (the blocks of a window all go
    # when the stream crosses a multiple of `window`, none before)
    tumbling: bool = False
    # lanes of one row where a row is every kv head's key (or value) side
    # by side, `kv_heads * head_dim` of them (`kv_pool_leaves`): the rows
    # the paged decode kernel reads. 0: rows of another make (a latent
    # that all heads share), read by XLA
    lanes: int = 0

    def pages(self, kv_pages: int) -> int:
        """Width of this kind's block table, where one a position's is
        `kv_pages` wide: the blocks that back one full-context slot's
        rows of it."""
        return -(-kv_pages // self.stride)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    num_layers: int = 12
    embed_dim: int = 768
    num_heads: int = 12
    mlp_dim: int | None = None          # default 4*embed_dim
    max_seq_len: int = 1024
    causal: bool = True                 # GPT-style; False for BERT/ViT
    dropout_rate: float = 0.0
    dtype: Dtype = jnp.bfloat16         # compute dtype (MXU)
    param_dtype: Dtype = jnp.float32
    attention: str = "dense"            # dense | pallas | ring | ulysses
    # Architecture dialect knobs (GPT-2/BERT/ViT use the defaults; the Llama
    # family — models/llama.py, the working replacement for the reference's
    # failed llama-7b auto-shard cell, 03_model_parallel.ipynb:86-89 — flips
    # all four). One shared core: every strategy applies to every dialect.
    norm: str = "layernorm"             # layernorm | rmsnorm
    # Normalization epsilon. Family presets pin the released models'
    # values (GPT-2/Llama 1e-5, BERT 1e-12) so torch-trained checkpoints
    # import bit-faithfully (models/torch_import.py).
    norm_eps: float = 1e-6
    # "pre" (GPT-2/Llama/ViT: x + Attn(LN(x))) or "post" (original
    # BERT: LN(x + Attn(x))) — released BERT checkpoints are post-LN, so
    # bert_config flips this for architectural fidelity.
    norm_position: str = "pre"          # pre | post
    # "sequential" (x + Attn(LN1(x)), then + FFN(LN2(.))) or "parallel"
    # (GPT-J's and Cohere's use_parallel_block: one norm, attention and
    # the feed-forward side by side, x + Attn(LN1(x)) + FFN(LN1(x)); no
    # ln2, and a router reads the one normed tensor)
    block_form: str = "sequential"      # sequential | parallel
    # GELU flavor: tanh approximation (GPT-2's "gelu_new", the flax
    # default) vs exact erf (BERT's "gelu").
    gelu_approximate: bool = True
    # Fused custom_vjp norm backward (ops/norms.py). A/B'd on the chip
    # (r5, BASELINE.md): wins only on post-LN BERT (+4.3% — twice the
    # LayerNorm sites per block); gpt2s wash, gpt2m/vit/llama small
    # losses. Default stays off; bert's bench config flips it on.
    fused_norms: bool = False
    # Fused chunked-CE head (ops/fused_ce.py) row-chunk size: rows of
    # fp32 logits alive at once (chunk x vocab x 4 B — 2048 x 32000 is
    # ~262 MB on Llama). Smaller chunks trade a little head throughput
    # for HBM headroom that can buy a bigger batch (the r5 llama bs-10
    # probe missed fitting by 32 MB at chunk 2048).
    ce_chunk: int = 2048
    # Flash/ring/ulysses kernel block size (block_q = block_k). None =
    # each kernel's own default — flash and ulysses 1024 (measured
    # fastest for the committed LM configs, BASELINE.md r3/r5), ring 512
    # (blocks tile the PER-SHARD sequence there). A per-config override
    # re-opens the block-size A/B without code edits.
    attn_block: int | None = None
    # Int8 quantized-training matmuls (ops/quant.py — AQT-style dynamic
    # per-channel scaling). "none" = bf16 dots (the committed baselines);
    # "int8_fwd" quantizes the forward weight matmuls (QKV/out, MLP, LM
    # head / fused-CE logits) and keeps the backward in bf16 — the
    # convergence-safe default for the MXU's ~2x int8 rate; "int8" also
    # quantizes both backward contractions with stochastic rounding on the
    # gradient operand. Sharding annotations are untouched: the injectable
    # dot_general is plain HLO, so TP's column/row splits, FSDP gathers and
    # the pipeline stage axis apply to the int8 operands unmodified.
    quant: str = "none"                 # none | int8_fwd | int8
    # Collective-latency hiding for the TP hot path (ops/overlap.py +
    # parallel/overlap.py — ISSUE 5). "xla": monolithic collectives, XLA's
    # latency-hiding scheduler does the overlap (the Trainer wires the
    # scheduler flags); "ring": route the QKV/out/MLP projections through
    # hand-decomposed collective-matmul rings (all-gather→matmul and
    # matmul→reduce-scatter as ppermute chains interleaved with the
    # chunks) whenever the ambient mesh has a tensor axis > 1 — the
    # ASPLOS'23 decomposition, wins at small tp axes / ICI-bound shapes;
    # "off": monolithic collectives AND no scheduler flags (the measured
    # baseline). Composes with quant: the ring gathers int8 shards
    # (comm bytes ÷4). Decode and pipeline stage bodies always take the
    # monolithic path (s=1 can't ring; stages already run inside a
    # manual region).
    overlap: str = "xla"                # ring | xla | off
    activation: str = "gelu"            # gelu | swiglu
    rope: bool = False                  # rotary position embedding (no
    #                                     learned pos table when True)
    rope_theta: float = 10000.0
    num_kv_heads: int | None = None     # < num_heads = grouped-query attn
    # a head's size where it is not embed_dim // num_heads (SmallThinker:
    # 28 heads of 128 on a width of 2,560)
    head_size: int | None = None
    use_bias: bool = True               # Llama: no biases anywhere
    # Autoregressive decode mode (inference.generate): attention keeps a
    # [b, max_seq_len, kv_heads, head_dim] K/V cache in the flax "cache"
    # collection and attends over it with a position mask; the embedder
    # tracks its own position counter. Same params as decode=False.
    decode: bool = False
    # Decode-time attention window: score only cache[:, :decode_attend_len]
    # instead of all max_seq_len slots. inference.generate sets it to the
    # (128-rounded) prompt+new total, so per-tick attention cost tracks the
    # sequence actually being generated, not the model's context limit —
    # at 8k context with a 1k generation that is an 8x score-work cut.
    # None = full max_seq_len. Caller contract: positions >= the window are
    # never live (generate guarantees total <= decode_attend_len).
    decode_attend_len: int | None = None
    # Slot-based decode (serving/ — the continuous-batching engine): > 0
    # turns every cache position counter ("index" per attention layer,
    # "pos_index" in the embedder) into a per-row [decode_slots] vector and
    # the cache writes into per-row dynamic_update_slices, so each batch
    # row ("slot") sits at its OWN sequence position — requests of
    # different lengths decode in one compiled step. Requires decode=True
    # and batch == decode_slots; 0 keeps the scalar counters generate()
    # uses (all rows advance together). Chunks of ANY length s decode
    # per-row (positions idx[row] + [0, s): within-chunk causality from
    # the position mask, writes land at [idx, idx+s)) and are
    # BITWISE-equal to s sequential single-token ticks — the multi-token
    # verify contract speculative decoding (ISSUE 8) builds on: a k+1
    # chunk whose suffix is later rejected needs no rollback, because the
    # next chunk's writes start at the accepted length and cover it.
    decode_slots: int = 0
    # Paged KV cache (serving/ — ISSUE 7, vLLM's PagedAttention realized
    # TPU-natively): kv_block_size > 0 replaces each attention layer's
    # dense [slots, max_seq_len, kv_heads, head_dim] cache with ONE pool
    # of kv_blocks fixed-size blocks, lane-dense: [kv_blocks,
    # kv_block_size, kv_heads*head_dim], one row a token with its kv
    # heads side by side (so the (8, 128) tile holds no padded lanes and
    # the row write, the gather and the Pallas kernel all see the array
    # row-major); a scanned stack keeps ONE [num_layers, ...] pool per K
    # and V at the stack level and carries it through the layer loop,
    # each layer writing at [layer, block, offset] in place. Plus a
    # per-slot block table ([decode_slots,
    # max_seq_len/kv_block_size] int32 physical-block ids, a "cache"
    # variable the serving engine overrides from host state every call).
    # Writes scatter each slot's token into table[slot, pos//bs] at offset
    # pos%bs; reads gather the slot's blocks back into position order, so
    # the masked attention math — and therefore the emitted tokens — stay
    # BITWISE-equal to the dense path while HBM is bounded by actual
    # resident tokens instead of slots x max_seq_len. Requires decode=True,
    # decode_slots >= 1 and max_seq_len % kv_block_size == 0 (block-padded
    # gathers then cover exactly the dense attend window, keeping the
    # softmax reduction shapes — hence the bits — identical). kv_blocks
    # sizes the pool (block 0 is the engine's reserved trash block).
    kv_block_size: int = 0
    kv_blocks: int = 0
    # KV compression (ISSUE 13). "bf16" stores pool blocks in cfg.dtype
    # (the exact-bitwise default); "int8" stores int8 codes plus fp32
    # per-(token, head) scale planes (`cached_key_scale` /
    # `cached_value_scale`, [kv_blocks, kv_block_size, kv_heads]) in the
    # same cache collection — absmax-over-head_dim quantization at block
    # write time (ops/quant.kv_quantize), dequantized at read. Per-row
    # scales mean the one-token-per-tick decode write never requantizes
    # block neighbours. ~1.9x resident tokens at equal pool HBM
    # (2 bytes/elem + 0 scale vs 1 byte/elem + 4/head_dim). Paged only.
    kv_dtype: str = "bf16"              # bf16 | int8
    # Sliding-window + attention-sink masking (StreamingLLM shape): when
    # kv_window_tokens > 0, query at position p attends position j iff
    # j < kv_sink_tokens or j > p - kv_window_tokens (the first sink
    # tokens plus the trailing window, p itself included). Both are
    # STATIC block multiples so the serving engine can retire
    # fully-dead middle blocks back to the allocator mid-stream without
    # retracing; masking lives in the compiled program, retirement is
    # pure host bookkeeping. 0 = full attention (the default).
    kv_sink_tokens: int = 0
    kv_window_tokens: int = 0
    # Decode-tick attention implementation for the paged pool: "gather"
    # reassembles each slot's blocks into position order and runs the
    # masked dense tail (bitwise-equal to the dense cache — the exact
    # contract); "pallas" runs the paged flash kernel
    # (ops/pallas_attention.paged_flash_attention) straight over the
    # block pool on single-token ticks — no gather materialization, each
    # slot's live blocks fetched by the kernel's own copies; the serving
    # default on TPU where kv_heads*head_dim is whole 128-lane tiles
    # (tolerance-pinned vs gather, not bitwise: online softmax
    # reassociates the reduction); an EVA tick calls it once a pool and
    # merges the two by their log-sum-exp (models/eva.py). Multi-token
    # chunks (prefill, speculative verify) always take the gather path.
    paged_attn: str = "gather"          # gather | pallas
    # Per-slot sink/window overrides (ISSUE 15): the slot-batch decode
    # models read sink/window from per-slot ``kv_sinks``/``kv_windows``
    # cache leaves (host-stamped by the serving engine) instead of the
    # static cfg values — what lets one request decode under a tighter
    # window than the pool's. Gather path only (the Pallas kernel takes
    # sink/window as STATIC parameters); off by default so the static
    # mask — and every pinned HLO — is byte-identical.
    per_slot_kv_limits: bool = False
    # EVA attention (models/eva.py; Zheng et al., ICLR 2023, as EvaByte
    # serves it): eva_window > 0 makes every layer attend, in ONE softmax,
    # the exact keys and values of the query's own tumbling window of
    # `eva_window` positions and one learned summary row (a key and a
    # value per head) for each chunk of `eva_chunk` positions of every
    # FINISHED window. Served through the paged engine only, where the
    # two kinds of row live in two pools of the same lane-dense layout
    # (`cache_kinds`): the window pool (`cached_key`/`cached_value` at
    # the blocks of ``window_table``, `window_blocks` of them, sized by
    # the engine) and the summary pool (`cached_summary_key`/`_value` at
    # the blocks of ``block_table``, `kv_blocks` of them, one row a
    # chunk).
    eva_window: int = 0
    eva_chunk: int = 0
    window_blocks: int = 0
    # Layers of several kinds in one scanned stack: `period` holds one
    # ``(rope, window)`` pair a layer of the pattern the stack repeats
    # (SmallThinker's four: ``(False, 0)``, a layer that attends every
    # position and has no positional encoding, then three of ``(True,
    # 4096)``, RoPE over a sliding window that counts the query itself).
    # The scanned body is then a whole period (`PeriodBlock`,
    # ``num_layers // len(period)`` of them), `rope` says only that the
    # embedder has no learned position table, and the two kinds keep
    # their rows in two pools of different depth (`cache_kinds`,
    # `pool_layers`): ``cached_key`` / ``cached_value`` at the blocks of
    # ``block_table`` for the full layers, which grows with the stream,
    # ``cached_window_key`` / ``cached_window_value`` at the blocks of
    # ``window_table`` (`window_blocks` of them, sized by the engine) for
    # the window layers, whose blocks the engine hands back once the
    # window has passed them. Served through the paged engine only
    # (models/periodic.py reads the pools). () = every layer alike. A
    # layer may instead be ``"mamba"``: a Mamba-1 mixer (models/ssm.py)
    # in place of attention, whose cache is one fixed state a slot
    # (`state_leaves`, the `CacheKind` without a table) and no rows.
    period: tuple = ()
    # the Mamba-1 mixer of a ``"mamba"`` layer: inner width `ssm_inner`
    # (d_inner), `ssm_state` states a channel, a causal depthwise
    # convolution of `ssm_conv` taps with a bias, the step's rank
    # `ssm_dt_rank` (models/ssm.py)
    ssm_inner: int = 0
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_dt_rank: int = 0
    # models/moe.py:DroplessMoE as every block's feed-forward
    # (`router_experts` > 0: the router's published width): the layer that
    # drops nothing, holds experts ``experts_held = (lo, hi)`` of them,
    # `experts_per_token` chosen a token, expert width `moe_dim`.
    # `moe_scoring` "sigmoid" is DeepSeek-V3's (scores ``sigmoid(logits)``,
    # chosen under a stored selection bias), "softmax" chooses the largest
    # logits and weighs them by a softmax over the chosen;
    # `moe_activation` is the gate's: "silu" (SwiGLU) or "relu" (ReGLU).
    # `router_input` "attn" routes from the block's FIRST normed tensor,
    # the one attention reads (SmallThinker: the router placed before
    # attention), "ffn" from the tensor the experts read.
    router_experts: int = 0
    experts_held: tuple = ()
    experts_per_token: int = 0
    moe_dim: int = 0
    moe_scoring: str = "sigmoid"        # sigmoid | softmax
    moe_activation: str = "silu"        # silu | relu
    router_input: str = "ffn"           # ffn | attn
    # `shared_experts` SwiGLUs (ReGLUs) of width `moe_dim` every token
    # passes through beside the routed ones, summed (a loader folds a
    # published average into their down projections)
    shared_experts: int = 0
    # what `DroplessMoE` reads besides and this stack has one value of
    # (no annotation: constants of the class, not fields): chosen scores
    # normalised, no further scale
    norm_topk_prob = True
    routed_scale = 1.0
    # `RMS(x) * (1 + g)`: the gain is stored about a unit offset
    norm_unit_offset: bool = False
    # the residual stream in float32 (the sublayers still compute in
    # `dtype`), and the logits summed and left in float32
    fp32_residual: bool = False
    fp32_logits: bool = False
    # Multi-token proposal heads (ISSUE 16, the Medusa recipe — Cai et
    # al. 2024) for a speculative DRAFT model: > 0 adds that many extra
    # decoding heads, each a zero-init SiLU residual block on the final
    # hidden state feeding the SHARED logit projection, so head j
    # predicts the token j+2 positions ahead and at init reproduces the
    # base head's distribution exactly. ONE draft forward then proposes
    # spec_heads+1 tokens instead of rolling the draft autoregressively —
    # inference.draft_and_verify collapses its k+1-step scan to a single
    # head-parallel forward when the draft carries heads. Never on the
    # TARGET model: the verify forward and the rejection kernel are
    # untouched, so losslessness does not depend on this knob.
    spec_heads: int = 0
    scan_layers: bool = True
    remat: bool = False
    # What the checkpoint keeps when remat=True. "full" recomputes the whole
    # block in backward (minimum memory, ~1/3 extra FLOPs). "dots" keeps the
    # outputs of weight matmuls (dot_generals with no batch dims — the
    # q/k/v/o projections and both MLP matmuls) and recomputes only
    # elementwise ops and attention internals: nearly the memory win at a
    # few percent recompute cost, the MFU-friendly default. "dots_norms"
    # additionally keeps the bf16 post-norm activations (see
    # checkpoint_policy).
    remat_policy: str = "dots"    # full | dots | dots_all | dots_norms
    tie_embeddings: bool = True
    # Pipeline parallelism (parallel/pipeline.py): >1 runs the stack as a
    # pipeline over the "pipe" mesh axis with this many stages.
    pipeline_stages: int = 1
    pipeline_microbatches: int = 1
    # "gpipe": forward pipeline, backward by AD — O(M) in-flight residuals.
    # "1f1b": fused train-step schedule (PipeDream-flush) — residuals bounded
    # by stage count; training only, selected by the Trainer's step builder
    # (the pure forward path always pipelines GPipe-style — schedules only
    # differ in where the backward interleaves).
    pp_schedule: str = "gpipe"
    # Mixture-of-Experts (models/moe.py:SwitchMoE, the layer that DROPS:
    # an assignment past an expert's capacity rides the residual): >0
    # replaces block MLPs with a top-k routed expert FFN bank, sharded
    # over the "expert" mesh axis. Use losses that add the sown
    # load-balance/z-loss terms
    # (training.losses.moe_token_cross_entropy_loss). The layer that
    # drops nothing and holds a share of its experts is
    # models/moe.py:DroplessMoE, configured by models/latent.py; none of
    # the moe_* options below reaches it.
    moe_experts: int = 0
    # SwitchMoE's capacity: ceil(cf * tokens_per_group / experts) slots an
    # expert; what overflows is dropped (and counted: `moe_overflow`).
    moe_capacity_factor: float = 1.25
    # 1 = Switch top-1 (raw top-prob gate); 2 = GShard-style top-2 with
    # gates renormalized over the chosen pair. First choices always beat
    # second choices in the capacity race (k-major cumsum ordering).
    moe_top_k: int = 1
    # An MoE FFN every Nth block ((i+1) % N == 0), dense MLP elsewhere.
    # N > 1 requires scan_layers=False: the scanned stack folds every
    # block into ONE body, so blocks cannot differ structurally.
    moe_every: int = 1
    # Routing groups G (per-group capacity ceil(cf · (tokens/G)/e)).
    # 0 = auto: one group per data×fsdp×expert shard when the expert
    # axis is > 1 — the layout whose dispatch is a pure permutation (a
    # literal all_to_all) — else 1, the original global-capacity
    # numerics. decode always routes per-token (capacity never binds →
    # serving output independent of slot neighbours, the bitwise
    # contract). Explicit values let single-device parity runs pin the
    # sharded grouping.
    moe_groups: int = 0
    # "auto" routes dispatch/combine through the explicit all_to_all
    # shard_map path (ops/overlap.expert_a2a_ffn) whenever mesh/shapes
    # tile; "a2a" documents intent (still falls back rather than error);
    # "dense" keeps the einsum path — the bench overlap-A/B knob.
    moe_dispatch: str = "auto"   # auto | a2a | dense
    # > 1 chunks the capacity dim so chunk i's combine a2a overlaps
    # chunk i+1's expert matmuls (the rings' latency-hiding recipe on
    # a2a). Non-dividing chunk counts degrade to monolithic.
    moe_chunks: int = 1

    @property
    def head_dim(self) -> int:
        return self.head_size or self.embed_dim // self.num_heads

    def __post_init__(self):
        if self.quant not in ("none", "int8_fwd", "int8"):
            raise ValueError(f"unknown quant {self.quant!r}; "
                             f"one of ('none', 'int8_fwd', 'int8')")
        from pytorchdistributed_tpu.parallel.overlap import validate_overlap

        validate_overlap(self.overlap)
        kv = self.kv_heads
        if kv <= 0 or self.num_heads % kv:
            raise ValueError(
                f"num_kv_heads {kv} must be a positive divisor of "
                f"num_heads {self.num_heads}")
        if self.decode and self.pipeline_stages > 1:
            raise ValueError("decode mode does not compose with pipeline "
                             "parallelism (generate on a dp/tp mesh instead)")
        if self.decode_slots < 0:
            raise ValueError(f"decode_slots {self.decode_slots} must be >= 0")
        if self.moe_dispatch not in ("auto", "a2a", "dense"):
            raise ValueError(f"unknown moe_dispatch {self.moe_dispatch!r}; "
                             f"one of ('auto', 'a2a', 'dense')")
        if self.moe_chunks < 1 or self.moe_every < 1 or self.moe_groups < 0:
            raise ValueError("moe_chunks/moe_every must be >= 1 and "
                             "moe_groups >= 0")
        if self.moe_experts > 0:
            if self.moe_top_k not in (1, 2):
                raise ValueError(
                    f"moe_top_k {self.moe_top_k} must be 1 (Switch) or 2 "
                    f"(GShard): models/moe.py:SwitchMoE races for "
                    f"capacity in those two orders only (top-k of any k, "
                    f"without drops, is models/moe.py:DroplessMoE)")
            if self.moe_top_k > self.moe_experts:
                raise ValueError(
                    f"moe_top_k {self.moe_top_k} needs at least that many "
                    f"experts (moe_experts={self.moe_experts})")
            if self.moe_every > 1 and self.scan_layers:
                raise ValueError(
                    "moe_every > 1 (interleaved MoE) requires "
                    "scan_layers=False: the scanned stack folds every "
                    "block into one body")
        if self.decode_slots > 0 and not self.decode:
            raise ValueError("decode_slots > 0 (slot-based decode) requires "
                             "decode=True")
        if self.kv_block_size < 0 or self.kv_blocks < 0:
            raise ValueError("kv_block_size / kv_blocks must be >= 0")
        if self.kv_block_size > 0:
            if not self.decode or self.decode_slots < 1:
                raise ValueError(
                    "paged KV (kv_block_size > 0) requires decode=True and "
                    "decode_slots >= 1 (the serving engine owns the slots)")
            if self.max_seq_len % self.kv_block_size:
                raise ValueError(
                    f"max_seq_len {self.max_seq_len} must be a multiple of "
                    f"kv_block_size {self.kv_block_size} (block-padded "
                    f"gathers must cover exactly the dense attend window)")
            if self.kv_blocks < 2:
                raise ValueError(
                    f"kv_blocks {self.kv_blocks} must be >= 2 (block 0 is "
                    f"the reserved trash block)")
        if self.kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"unknown kv_dtype {self.kv_dtype!r}; "
                             f"one of ('bf16', 'int8')")
        if self.kv_dtype == "int8" and not self.kv_block_size:
            raise ValueError(
                "kv_dtype='int8' requires the paged KV pool "
                "(kv_block_size > 0): the scale planes are block-shaped")
        if self.paged_attn not in ("gather", "pallas"):
            raise ValueError(f"unknown paged_attn {self.paged_attn!r}; "
                             f"one of ('gather', 'pallas')")
        if self.paged_attn == "pallas" and not self.kv_block_size:
            raise ValueError("paged_attn='pallas' requires the paged KV "
                             "pool (kv_block_size > 0)")
        if self.kv_sink_tokens < 0 or self.kv_window_tokens < 0:
            raise ValueError("kv_sink_tokens / kv_window_tokens must be "
                             ">= 0")
        if self.kv_sink_tokens and not self.kv_window_tokens:
            raise ValueError(
                "kv_sink_tokens without kv_window_tokens is full attention "
                "with extra steps — set kv_window_tokens > 0 to enable the "
                "sliding window, or drop the sinks")
        if self.kv_window_tokens:
            if not self.kv_block_size:
                raise ValueError(
                    "sliding-window KV (kv_window_tokens > 0) requires the "
                    "paged pool (kv_block_size > 0): retirement returns "
                    "whole blocks to the allocator")
            if (self.kv_window_tokens % self.kv_block_size
                    or self.kv_sink_tokens % self.kv_block_size):
                raise ValueError(
                    f"kv_window_tokens {self.kv_window_tokens} and "
                    f"kv_sink_tokens {self.kv_sink_tokens} must be "
                    f"multiples of kv_block_size {self.kv_block_size} "
                    f"(retirement is whole-block)")
        if self.block_form not in ("sequential", "parallel"):
            raise ValueError(f"unknown block_form {self.block_form!r}; "
                             f"one of ('sequential', 'parallel')")
        if self.block_form == "parallel" and self.norm_position != "pre":
            raise ValueError("block_form='parallel' is a pre-norm block: "
                             "one norm both sublayers read")
        if self.fused_norms and self.norm == "layernorm" and not self.use_bias:
            raise ValueError("the fused LayerNorm (fused_norms) is built "
                             "with a bias; use_bias=False asks for none")
        if self.shared_experts < 0:
            raise ValueError(f"shared_experts must be >= 0, got "
                             f"{self.shared_experts}")
        if self.eva_window:
            self._check_eva()
        if self.period:
            self._check_period()
        if self.router_experts:
            self._check_dropless()
        if self.norm_unit_offset and (self.norm != "rmsnorm"
                                      or self.fused_norms):
            raise ValueError("norm_unit_offset is built for the plain "
                             "RMSNorm (norm='rmsnorm', fused_norms=False)")
        if self.spec_heads < 0:
            raise ValueError(f"spec_heads must be >= 0, got "
                             f"{self.spec_heads}")
        if self.decode_attend_len is not None and (
                self.decode_attend_len < 1
                or self.decode_attend_len > self.max_seq_len):
            raise ValueError(
                f"decode_attend_len {self.decode_attend_len} must be in "
                f"[1, max_seq_len={self.max_seq_len}]")
        if self.decode and self.attention != "dense":
            # The decode path runs its own masked attention over the KV
            # cache; the training-time backend knob does not apply there.
            import warnings

            warnings.warn(
                f"decode=True always uses the cache-masked dense path; "
                f"attention={self.attention!r} is ignored during decode "
                f"(build the decode model with attention='dense' to "
                f"silence this)", stacklevel=3)

    def _check_eva(self) -> None:
        win, chunk, bs = self.eva_window, self.eva_chunk, self.kv_block_size
        if chunk < 1 or win % chunk:
            raise ValueError(f"eva_chunk {chunk} must divide eva_window "
                             f"{win}")
        if self.kv_heads != self.num_heads:
            raise ValueError("EVA summarises per head: grouped-query "
                             "heads (num_kv_heads < num_heads) are not "
                             "built")
        if not self.rope or not self.scan_layers:
            raise ValueError("EVA attention is built for the scanned RoPE "
                             "stack (rope=True, scan_layers=True)")
        if self.decode and not bs:
            raise ValueError(
                "a model with two cache kinds is served through the "
                "paged engine only (block_size > 0): the dense per-slot "
                "cache has one layout for every position")
        if self.kv_dtype != "bf16" or self.kv_window_tokens:
            raise ValueError(
                "kv_dtype='int8' and kv_window_tokens are not built for "
                "EVA's two pools: a summary row is computed from the "
                "stored rows, and the window is the model's own "
                "(eva_window), retired a whole window at a time")
        if bs:
            if bs % chunk or win % bs or self.max_seq_len % win:
                raise ValueError(
                    f"kv_block_size {bs} must be a multiple of eva_chunk "
                    f"{chunk} (a chunk's rows lie in one block) and "
                    f"divide eva_window {win}, which must divide "
                    f"max_seq_len {self.max_seq_len}")
            if self.window_blocks < 2:
                raise ValueError("window_blocks must be >= 2 (block 0 of "
                                 "the window pool is its trash block)")

    def _check_period(self) -> None:
        attn = self._attention_layers
        if any(e[0] == "mamba" for e in attn):
            raise ValueError(
                f"period {self.period}: a mamba layer is \"mamba\" alone: "
                f"it has no window and no rotation, its state is the "
                f"whole past of the stream")
        windows = {w for _, w in attn if w}
        if (not attn or len(windows) > 1 or min(w for _, w in attn) != 0
                or self.num_layers % len(self.period)):
            raise ValueError(
                f"period {self.period}: (rope, window) or \"mamba\" a "
                f"layer, with a layer that attends every position (the "
                f"stream's own pool is the one that never retires), one "
                f"window size for the others, and num_layers "
                f"{self.num_layers} a multiple of its length")
        if "mamba" in self.period:
            self._check_mamba()
        if not self.scan_layers or self.eva_window or not self.rope:
            raise ValueError("a period of layer kinds is built for the "
                             "scanned stack without a learned position "
                             "table (scan_layers=True, rope=True), and "
                             "not beside EVA")
        if self.decode and not self.kv_block_size:
            raise ValueError(
                "a model with two cache kinds is served through the "
                "paged engine only (block_size > 0): the dense per-slot "
                "cache has one layout for every layer")
        if self.kv_dtype != "bf16" and "mamba" in self.period:
            raise ValueError(
                "kv_dtype='int8' is not built beside a recurrent state: "
                "a mamba layer's state is summed into at every position "
                "and kept in float32, and int8 codes with a scale a row "
                "would round it anew every step")
        if (self.kv_dtype != "bf16" or self.kv_window_tokens
                or self.kv_sink_tokens):
            raise ValueError(
                "kv_dtype='int8' and kv_window_tokens / kv_sink_tokens "
                "are not built for a period's two pools: the window is "
                "its window layers' own and is retired per kind")
        if self.kv_block_size and windows:
            (win,) = windows
            if win % self.kv_block_size:
                raise ValueError(
                    f"the period's window {win} must be a multiple of "
                    f"kv_block_size {self.kv_block_size} (the paged "
                    f"decode kernel's window is whole blocks)")
            if self.window_blocks < 2:
                raise ValueError("window_blocks must be >= 2 (block 0 of "
                                 "the window pool is its trash block)")

    def _check_mamba(self) -> None:
        if (self.ssm_inner < 1 or self.ssm_dt_rank < 1
                or self.ssm_state < 1 or self.ssm_conv < 2):
            raise ValueError(
                f"a mamba layer needs ssm_inner and ssm_dt_rank, at least "
                f"one state and a convolution of two taps; got "
                f"{self.ssm_inner}, {self.ssm_dt_rank}, {self.ssm_state}, "
                f"{self.ssm_conv}")

    def _check_dropless(self) -> None:
        if self.moe_experts:
            raise ValueError("router_experts (DroplessMoE) and "
                             "moe_experts (SwitchMoE) are two layers: "
                             "set one")
        lo, hi = self.experts_held or (0, 0)
        if not (0 <= lo < hi <= self.router_experts
                and 0 < self.experts_per_token <= self.router_experts
                and self.moe_dim > 0):
            raise ValueError(
                f"DroplessMoE needs experts_held (lo, hi) inside the "
                f"router's {self.router_experts}, experts_per_token and "
                f"moe_dim; got {self.experts_held}, "
                f"{self.experts_per_token}, {self.moe_dim}")
        for name, allowed in (("moe_scoring", ("sigmoid", "softmax")),
                              ("moe_activation", ("silu", "relu")),
                              ("router_input", ("ffn", "attn"))):
            if getattr(self, name) not in allowed:
                raise ValueError(f"unknown {name} "
                                 f"{getattr(self, name)!r}; one of "
                                 f"{allowed}")
        if self.router_input == "attn" and self.norm_position != "pre":
            raise ValueError("router_input='attn' reads the pre-norm "
                             "block's first normed tensor")

    @property
    def kv_heads(self) -> int:
        return (self.num_kv_heads if self.num_kv_heads is not None
                else self.num_heads)

    @property
    def cache_kinds(self) -> tuple:
        """The pools the serving engine keeps, the stream's own first;
        every one holds per-head key and value rows (`kv_pool_leaves`)."""
        lanes = self.kv_heads * self.head_dim
        if self.eva_window:
            return (CacheKind("summary", "block_table",
                              stride=self.eva_chunk, lanes=lanes),
                    CacheKind("window", "window_table", self.eva_window,
                              tumbling=True, lanes=lanes))
        # a recurrent state a slot beside the stream's own pool (one kind
        # of rows: the kind keeps the single pool's name)
        state = ((CacheKind("state", None),) if "mamba" in self.period
                 else ())
        window = max((w for _, w in self._attention_layers), default=0)
        if window:
            return (CacheKind("full", "block_table", lanes=lanes),
                    CacheKind("window", "window_table", window,
                              lanes=lanes)) + state
        return (CacheKind(None, "block_table", lanes=lanes),) + state

    @property
    def counter_names(self) -> tuple:
        """Names of the "counters" collection's one vector: what the
        stack counts on the device a call (the engine's `summary()` sums
        the ticks')."""
        if self.eva_window:
            from pytorchdistributed_tpu.models import eva

            return eva.COUNTERS
        names = ()
        if self.router_experts:
            from pytorchdistributed_tpu.models.moe import DROPLESS_COUNTERS

            names += DROPLESS_COUNTERS
        if self.period:
            from pytorchdistributed_tpu.models import periodic

            names += periodic.COUNTERS
        if "mamba" in self.period:
            from pytorchdistributed_tpu.models import ssm

            names += ssm.COUNTERS
        return names

    @property
    def kv_pages(self) -> int:
        """Block-table width: blocks needed to back one full-context slot
        (0 when the dense decode cache is in use)."""
        if not self.kv_block_size:
            return 0
        return self.max_seq_len // self.kv_block_size

    @property
    def kv_pool_leaves(self) -> dict:
        """name -> (shape, dtype) of one layer's paged-pool leaves: the
        K/V rows and, on an int8 pool, the fp32 dequant scale per written
        (token, head) — same "cache" collection, so the engine's block
        gather/scatter, export/import and prefix shipping carry the
        scales with the codes."""
        rows = (self.kv_blocks, self.kv_block_size)
        int8 = self.kv_dtype == "int8"
        kv = (rows + (self.kv_heads * self.head_dim,),
              jnp.int8 if int8 else self.dtype)
        if self.eva_window:
            # the window's exact rows and the chunks' summary rows: the
            # same row layout, a pool each
            win = ((self.window_blocks,) + kv[0][1:], kv[1])
            return {"cached_key": win, "cached_value": win,
                    "cached_summary_key": kv, "cached_summary_value": kv}
        leaves = {"cached_key": kv, "cached_value": kv}
        if any(w for _, w in self._attention_layers):
            # the window layers' rows: the same layout, a pool of their own
            win = ((self.window_blocks,) + kv[0][1:], kv[1])
            leaves.update(cached_window_key=win, cached_window_value=win)
        if int8:
            scale = (rows + (self.kv_heads,), jnp.float32)
            leaves.update(cached_key_scale=scale, cached_value_scale=scale)
        return leaves

    @property
    def state_leaves(self) -> dict:
        """name -> (shape, dtype) of one mamba layer's recurrent state,
        a slot's row each (models/ssm.py): the scan's state ``[state,
        inner]`` (the channels on the lanes) and the convolution's last
        ``ssm_conv - 1`` inputs, side by side in one row. Empty where no
        layer is a mamba layer."""
        if "mamba" not in self.period:
            return {}
        slots = self.decode_slots
        return {"cached_ssm_state": ((slots, self.ssm_state,
                                      self.ssm_inner), jnp.float32),
                "cached_conv_state": ((slots, (self.ssm_conv - 1)
                                       * self.ssm_inner), self.dtype)}

    @property
    def _attention_layers(self) -> list:
        """The ``(rope, window)`` entries of `period`, its mamba layers
        left out."""
        return [e for e in self.period if e != "mamba"]

    def layer_kind(self, entry) -> str:
        """A `period` entry's kind: "mamba", "window" or "full"."""
        if entry == "mamba":
            return "mamba"
        return "window" if entry[1] else "full"

    def pool_layers(self, name: str) -> int:
        """Layers whose rows (or states) the scanned stack's leaf `name`
        holds: every layer's, unless the layers come in kinds (`period`),
        each with a pool as deep as the kind has layers."""
        if not self.period:
            return self.num_layers
        kind = ("mamba" if name in self.state_leaves
                else "window" if name.startswith("cached_window")
                else "full")
        of_kind = sum(1 for e in self.period if self.layer_kind(e) == kind)
        return self.num_layers // len(self.period) * of_kind

    @property
    def ffn_dim(self) -> int:
        return self.mlp_dim if self.mlp_dim is not None else 4 * self.embed_dim


def gather_free_ce(logits, targets):
    """Per-position cross-entropy [b, s] via logsumexp − one-hot
    contraction. Gather-free on purpose: under TP the vocab dim is
    tensor-sharded, and a take-along-axis gather on a sharded dim inside a
    manual-axis shard_map (the 1F1B pipeline) crashes XLA's SPMD
    partitioner; the one-hot contraction partitions cleanly (Megatron's
    vocab-parallel CE shape) and XLA reduces it to the same FLOPs."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.einsum(
        "bsv,bsv->bs", logits,
        jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32))
    return lse - true


def checkpoint_policy(name: str):
    """Map a remat_policy name to a jax.checkpoint policy (None = save
    nothing, recompute everything)."""
    cp = jax.checkpoint_policies
    # attn_out/attn_lse are named inside the flash kernel's vjp fwd
    # (ops/pallas_attention.py): saving them spares the backward a full
    # re-run of the attention forward per layer.
    attn_saved = cp.save_only_these_names("attn_out", "attn_lse")
    policies = {
        "full": None,
        "dots": cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable, attn_saved),
        "dots_all": cp.save_from_both_policies(
            cp.dots_saveable, attn_saved),
        # dots_all + the bf16 post-norm activations (norm_out, named in
        # TransformerBlock): trades one bf16 activation of HBM per norm
        # for skipping the fp32-upcast + cross-lane-reduce norm recompute
        # the r3 profile put at ~10% of the Llama-1B step. Unmeasured on
        # hardware as of r3 (chip access dropped) — benchmark before
        # making it a default.
        "dots_norms": cp.save_from_both_policies(
            cp.dots_saveable,
            cp.save_only_these_names("attn_out", "attn_lse", "norm_out")),
    }
    if name not in policies:
        raise ValueError(
            f"unknown remat_policy {name!r}; one of {sorted(policies)}")
    return policies[name]


def _attention_fn(kind: str) -> Callable:
    if kind == "dense":
        return dense_attention
    if kind == "pallas":
        from pytorchdistributed_tpu.ops.pallas_attention import (
            flash_attention_sharded,
        )
        return flash_attention_sharded
    if kind == "ring":
        from pytorchdistributed_tpu.ops.ring_attention import (
            ring_attention_sharded,
        )
        return ring_attention_sharded
    if kind == "ulysses":
        from pytorchdistributed_tpu.ops.ulysses import ulysses_attention
        return ulysses_attention
    raise ValueError(f"unknown attention backend {kind!r}")


def _cfg_dot_general(cfg, default=None):
    """The config's injectable contraction: None/``default`` for
    quant="none", else ops.quant's shared int8 dot_general. One accessor
    so every weight-matmul site (Dense, fused projections, LM heads,
    fused-CE) flips together with the flag."""
    from pytorchdistributed_tpu.ops.quant import dot_general_for

    return dot_general_for(cfg.quant) or default


def _site_dot_general(cfg, parallel, default=None):
    """Per-site contraction for the TP projections: with
    ``cfg.overlap == "ring"`` and a parallel kind declared, the
    ring-routing injectable (parallel/overlap.py — falls back to the
    monolithic/quant path at trace time when no ring applies); otherwise
    exactly `_cfg_dot_general`. ``parallel`` is "column" (w's feature dim
    tensor-sharded) or "row" (contraction dim tensor-sharded), per the
    Megatron decomposition the kernel's logical axes already declare."""
    if parallel is None:
        return _cfg_dot_general(cfg, default)
    from pytorchdistributed_tpu.parallel.overlap import site_dot_general

    return site_dot_general(cfg, parallel, default)


def _dense_general(features: int, kernel_axes, cfg, name, *,
                   use_bias: bool = True, parallel: str | None = None):
    """Dense with logically-partitioned kernel. Head projections keep heads
    flattened into the feature dim (kernel [embed, heads*head_dim] with
    logical axes (embed, heads)): sharding "heads" over the tensor axis then
    splits whole heads, the Megatron attention shard. ``parallel`` names
    the site's Megatron role so overlap="ring" can route it through the
    matching collective-matmul ring."""
    return nn.Dense(
        features,
        use_bias=use_bias,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        dot_general=_site_dot_general(cfg, parallel),
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=0.02), kernel_axes
        ),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), kernel_axes[-1:]
        ),
        name=name,
    )


#: the fused kernels by their name in a checkpoint, and the name each is
#: served under as planes (`fused_kernel`)
PLANES = {f"{name}_kernel": f"{name}_planes" for name in ("qkv", "kv", "wi")}


def fused_kernel(module, name, stack, width, axes):
    """`stack` matrices of `[embed, width]` applied side by side to the
    `[batch, seq, embed]` input, and the einsum that applies them to it
    (`[batch, seq, stack, width]` out, the matrices' order kept).

    Initialised, trained and checkpointed as ``<name>_kernel [embed,
    stack, width]``, logical `axes`. The serving engine holds a scanned
    stack's as ``<name>_planes [stack, embed, width]``
    (serving/weights.py:served): the scan's slice of the stacked leaf
    is then `stack` contiguous matrices that the product reads where they
    lie, where the fused axis as the second-minor dimension has XLA copy
    the layer's whole kernel into another layout first. The same numbers
    in the same sums: which name the tree holds says which to read."""
    cfg = module.cfg
    init = nn.initializers.normal(stddev=0.02)
    if module.has_variable("params", PLANES[f"{name}_kernel"]):
        planes = module.param(
            PLANES[f"{name}_kernel"],
            nn.with_logical_partitioning(init, (axes[1], axes[0], axes[2])),
            (stack, cfg.embed_dim, width), cfg.param_dtype)
        return planes, "bse,cef->bscf"
    kernel = module.param(
        f"{name}_kernel", nn.with_logical_partitioning(init, axes),
        (cfg.embed_dim, stack, width), cfg.param_dtype)
    return kernel, "bse,ecf->bscf"


class SelfAttention(nn.Module):
    """Multi-head self-attention with Megatron-ready head sharding.

    ``deterministic`` is a module attribute (not a call arg) so lifted
    transforms (nn.remat / nn.scan) see only array arguments —
    jax.checkpoint cannot mark keyword-only args static.

    ``paging`` is the paged stack's per-slot state, which every layer
    reads alike (``TransformerStack``: ``index``, ``block_table`` and,
    under per-slot limits, ``kv_sinks``/``kv_windows``).
    ``pool``/``layer`` are the scanned stack's paged KV pool (a dict of
    layer-stacked leaves, ``TransformerConfig.kv_pool_leaves``) and this
    layer's index in it: the layer writes its rows at ``[layer, block,
    offset]`` and the call returns ``(out, pool)``. Left at None, a paged
    layer owns its pool as "cache" variables (the unrolled stack).

    ``rope`` and ``window`` are this layer's own where the layers come in
    kinds (`TransformerConfig.period`): whether it rotates its queries
    and keys, and the positions a query sees, itself included (0: every
    one); ``layer`` is then its index in its kind's pool. None / 0: the
    config's, as every layer of a uniform stack.
    """

    cfg: TransformerConfig
    deterministic: bool = True
    rope: bool | None = None
    window: int = 0

    @nn.compact
    def __call__(self, x, paging=None, pool=None, layer=None):
        cfg = self.cfg
        deterministic = self.deterministic
        b, s, _ = x.shape
        # One fused [embed, 3, heads·head_dim] projection instead of three
        # [embed, heads·head_dim] matmuls: N=768-class matmuls run the MXU
        # at a fraction of its rate on v5e (measured 18 vs 43+ TFLOP/s), so
        # folding q/k/v into one dot is a direct step-time win. The q/k/v
        # stack rides its own *unsharded* kernel dim, so under TP the
        # "heads" dim still splits whole heads and every device holds the
        # q, k and v of its heads locally (the Megatron attention shard).
        # Explicit params: nn.DenseGeneral flattens multi-dim features for
        # its kernel init, which breaks rank-3 logical partitioning.
        # Grouped-query attention (kv_heads < num_heads) splits into a q
        # kernel + a fused [embed, 2, kv_heads·head_dim] kv kernel — both
        # still shard whole heads on the "heads" logical axis (served as
        # planes from a scanned stack: `fused_kernel`).
        def heads(t, n):
            t = t.reshape(b, s, n, cfg.head_dim)
            return nn.with_logical_constraint(
                t, (Logical.BATCH, Logical.SEQ, Logical.HEADS, Logical.KV))

        def fused_proj(name, stack, width):
            if stack > 1:
                kernel, eq = fused_kernel(
                    self, name, stack, width,
                    (Logical.EMBED, None, Logical.HEADS))
            else:
                kernel = self.param(
                    f"{name}_kernel",
                    nn.with_logical_partitioning(
                        nn.initializers.normal(stddev=0.02),
                        (Logical.EMBED, Logical.HEADS)),
                    (cfg.embed_dim, width), cfg.param_dtype)
                eq = "bse,ef->bsf"
            out = jnp.einsum(eq, x, kernel.astype(cfg.dtype),
                             _dot_general=_site_dot_general(
                                 cfg, "column", jax.lax.dot_general))
            if cfg.use_bias:
                bias = self.param(
                    f"{name}_bias",
                    nn.with_logical_partitioning(
                        nn.initializers.zeros_init(),
                        (None, Logical.HEADS) if stack > 1
                        else (Logical.HEADS,)),
                    (stack, width) if stack > 1 else (width,),
                    cfg.param_dtype,
                )
                out = out + bias.astype(cfg.dtype)
            return out

        if cfg.kv_heads == cfg.num_heads:
            fused = fused_proj("qkv", 3, cfg.num_heads * cfg.head_dim)
            q = heads(fused[..., 0, :], cfg.num_heads)
            k = heads(fused[..., 1, :], cfg.num_heads)
            v = heads(fused[..., 2, :], cfg.num_heads)
        else:
            q = heads(fused_proj("q", 1, cfg.num_heads * cfg.head_dim),
                      cfg.num_heads)
            kv = fused_proj("kv", 2, cfg.kv_heads * cfg.head_dim)
            k = heads(kv[..., 0, :], cfg.kv_heads)
            v = heads(kv[..., 1, :], cfg.kv_heads)

        if cfg.decode:
            # slot-based decode (serving/): the position counter is a
            # per-row [decode_slots] vector — each slot advances alone
            if cfg.decode_slots and b != cfg.decode_slots:
                raise ValueError(
                    f"slot-decode batch {b} != decode_slots "
                    f"{cfg.decode_slots} (the engine owns the batch dim)")
            if cfg.kv_block_size:
                idx = paging["index"]
            else:
                idx_var = self.variable(
                    "cache", "index",
                    lambda: jnp.zeros((cfg.decode_slots,)
                                      if cfg.decode_slots else (),
                                      jnp.int32))
                idx = idx_var.value
        if cfg.rope if self.rope is None else self.rope:
            cos, sin = rope_tables(cfg.max_seq_len, cfg.head_dim,
                                   cfg.rope_theta)
            if cfg.decode and cfg.decode_slots:
                # per-row offsets: gather [b, s] positions from the tables
                pos = idx[:, None] + jnp.arange(s)
                cos, sin = cos[pos], sin[pos]          # [b, s, d/2]
            elif cfg.decode:
                cos = jax.lax.dynamic_slice_in_dim(cos, idx, s)
                sin = jax.lax.dynamic_slice_in_dim(sin, idx, s)
            else:
                cos, sin = cos[:s], sin[:s]
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)

        rep = cfg.num_heads // cfg.kv_heads

        if cfg.eva_window:
            # EVA (models/eva.py): the window's exact rows and the
            # finished windows' summary rows under one softmax, both read
            # from the scanned stack's pools; `phi` scores a chunk's keys
            # for its summary, `mu` is added to the summary's key
            if not (cfg.decode and cfg.kv_block_size) or pool is None:
                raise NotImplementedError(
                    "EVA attention is served through the paged engine "
                    "(ServingEngine(model, params, block_size=...)); a "
                    "cacheless forward is the benchmark's plain reference")
            from pytorchdistributed_tpu.models import eva

            phi, mu = (self.param(
                name, nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.1),
                    (Logical.HEADS, Logical.KV)),
                (cfg.num_heads, cfg.head_dim), jnp.float32)
                for name in ("eva_phi", "eva_mu"))
            out, pool = eva.paged_attention(cfg, q, k, v, phi, mu, paging,
                                            pool, layer)
        elif cfg.period:
            # layers of two kinds (models/periodic.py): this one's rows
            # go to its kind's pool and are read from there, all of the
            # stream's or the window's
            if not (cfg.decode and cfg.kv_block_size) or pool is None:
                raise NotImplementedError(
                    "a period of full and window layers is served "
                    "through the paged engine (ServingEngine(model, "
                    "params, block_size=...)); a cacheless forward is "
                    "the benchmark's plain reference")
            from pytorchdistributed_tpu.models import periodic

            out, pool = periodic.paged_attention(cfg, q, k, v, paging,
                                                 pool, layer, self.window)
        elif cfg.decode:
            if cfg.kv_block_size:
                # Paged KV (ISSUE 7): one pool of fixed-size blocks shared
                # by every slot + a per-slot block table mapping logical
                # block p//bs to a physical pool block. Table and
                # positions are the stack's: the serving engine
                # overrides them from host state on every compiled call,
                # which is what makes prefix reuse and copy-free
                # admission pure host-side bookkeeping. Falls through to
                # the SAME masked-attention tail as the dense layout:
                # only where K/V rows live differs, which is what keeps
                # paged outputs bitwise-equal to dense.
                bs_blk = cfg.kv_block_size
                table = paging["block_table"]
                own = None
                if pool is None:
                    own = {name: self.variable("cache", name, jnp.zeros,
                                               shape, dtype)
                           for name, (shape, dtype)
                           in cfg.kv_pool_leaves.items()}
                    pool = {name: var.value for name, var in own.items()}
                int8 = cfg.kv_dtype == "int8"
                if not self.is_initializing():
                    # scatter each row's s tokens into its table's blocks,
                    # in place; positions past the context (padded prefill
                    # tails) drop into the reserved trash block 0 instead
                    # of clamping onto a live row
                    pos = idx[:, None] + jnp.arange(s)           # [b, s]
                    inb = jnp.clip(pos // bs_blk, 0, cfg.kv_pages - 1)
                    blk = jnp.take_along_axis(table, inb, axis=1)
                    blk = jnp.where(pos < cfg.max_seq_len, blk, 0)
                    at = (blk, pos % bs_blk)
                    if layer is not None:
                        at = (layer,) + at
                    rows = {"cached_key": k, "cached_value": v}
                    if int8:
                        from pytorchdistributed_tpu.ops.quant import (
                            kv_quantize,
                        )

                        rows["cached_key"], rows["cached_key_scale"] = (
                            kv_quantize(k))
                        rows["cached_value"], rows["cached_value_scale"] = (
                            kv_quantize(v))
                    pool = {
                        name: leaf.at[at].set(
                            rows[name].reshape(b, s, leaf.shape[-1])
                            .astype(leaf.dtype))
                        for name, leaf in pool.items()}
                    if own is not None:
                        for name, var in own.items():
                            var.value = pool[name]
                attend = cfg.decode_attend_len or cfg.max_seq_len
                na = -(-attend // bs_blk)
                attend = na * bs_blk
                if cfg.paged_attn == "pallas" and s == 1:
                    # decode tick on the Pallas paged kernel: q attends
                    # the pool STRAIGHT through the block table — the
                    # gathered [slots, attend, ...] copy below never
                    # materializes. One program a slot copies the slot's
                    # live blocks out of the pool, a tile of table
                    # entries at a time, and computes those alone: the
                    # call costs what the live tokens cost, not what
                    # the table could hold. Tolerance-pinned vs the
                    # gather path (online softmax reassociates); chunks
                    # (s > 1: prefill, spec verify) stay on the gather
                    # tail.
                    from pytorchdistributed_tpu.ops.pallas_attention import (
                        paged_flash_attention,
                    )

                    out = paged_flash_attention(
                        q[:, 0], pool["cached_key"], pool["cached_value"],
                        table[:, :na], idx, layer=layer,
                        k_scale=pool.get("cached_key_scale"),
                        v_scale=pool.get("cached_value_scale"),
                        sink_tokens=cfg.kv_sink_tokens,
                        window_tokens=cfg.kv_window_tokens,
                    )[:, None].astype(cfg.dtype)
                    kc = vc = None
                else:
                    # gather the attended blocks back into position
                    # order: with max_seq_len % bs == 0 the gathered
                    # window is exactly the dense attend window, so every
                    # reduction below keeps its shape — the bitwise-
                    # parity property the serving tests pin
                    def gathered(name):
                        return paged_gather(pool[name], table[:, :na],
                                            layer)

                    def heads(rows):
                        return rows.reshape(b, attend, cfg.kv_heads,
                                            cfg.head_dim)

                    kc = heads(gathered("cached_key"))
                    vc = heads(gathered("cached_value"))
                    if int8:
                        from pytorchdistributed_tpu.ops.quant import (
                            kv_dequantize,
                        )

                        kc = kv_dequantize(
                            kc, gathered("cached_key_scale"), cfg.dtype)
                        vc = kv_dequantize(
                            vc, gathered("cached_value_scale"), cfg.dtype)
            else:
                cached_k = self.variable(
                    "cache", "cached_key", jnp.zeros,
                    (b, cfg.max_seq_len, cfg.kv_heads, cfg.head_dim),
                    cfg.dtype)
                cached_v = self.variable(
                    "cache", "cached_value", jnp.zeros,
                    (b, cfg.max_seq_len, cfg.kv_heads, cfg.head_dim),
                    cfg.dtype)
                if not self.is_initializing():
                    if cfg.decode_slots:
                        # per-row writes: each slot lands at its own
                        # position (vmapped dynamic_update_slice lowers to
                        # a scatter)
                        row = lambda c, u, i: jax.lax.dynamic_update_slice(  # noqa: E731
                            c, u, (i, 0, 0))
                        cached_k.value = jax.vmap(row)(
                            cached_k.value, k.astype(cfg.dtype), idx)
                        cached_v.value = jax.vmap(row)(
                            cached_v.value, v.astype(cfg.dtype), idx)
                    else:
                        cached_k.value = jax.lax.dynamic_update_slice(
                            cached_k.value, k.astype(cfg.dtype),
                            (0, idx, 0, 0))
                        cached_v.value = jax.lax.dynamic_update_slice(
                            cached_v.value, v.astype(cfg.dtype),
                            (0, idx, 0, 0))
                    idx_var.value = idx + s
                # Static attention window (decode_attend_len): the cache
                # stays max_seq_len-sized, but scores only cover the slots
                # generation can actually reach — generate() sets the
                # bound from prompt_len + max_new_tokens.
                attend = cfg.decode_attend_len or cfg.max_seq_len
                kc = cached_k.value[:, :attend]
                vc = cached_v.value[:, :attend]
            if kc is not None:
                if rep > 1:
                    kc = jnp.repeat(kc, rep, axis=2)
                    vc = jnp.repeat(vc, rep, axis=2)
                # Masked dense attention over the live window: the
                # current chunk's token i (absolute position idx+i) sees
                # cache slots j <= idx+i. fp32 softmax like the training
                # backends. (slot decode: idx is [b], so pos/valid grow a
                # leading row dim — each slot masks against its own
                # position)
                pos = (idx[:, None] if cfg.decode_slots
                       else idx) + jnp.arange(s)
                valid = jnp.arange(attend) <= pos[..., None]
                if cfg.kv_window_tokens:
                    # sink + sliding window (StreamingLLM shape): keep
                    # the first sink tokens plus the trailing window —
                    # the positions outside are exactly the rows the
                    # engine retires to the allocator, so the gathered
                    # garbage there is masked before the softmax
                    j = jnp.arange(attend)
                    if cfg.per_slot_kv_limits and cfg.kv_block_size:
                        # per-slot values (ISSUE 15): with every slot at
                        # the cfg defaults this computes the identical
                        # valid mask, so untouched streams stay bitwise
                        snk = paging["kv_sinks"][:, None, None]
                        win = paging["kv_windows"][:, None, None]
                        valid &= ((j[None, None, :] < snk)
                                  | (j[None, None, :]
                                     > pos[..., None] - win))
                    else:
                        valid &= ((j < cfg.kv_sink_tokens)
                                  | (j > pos[..., None]
                                     - cfg.kv_window_tokens))
                scores = jnp.einsum("bihd,bjhd->bhij", q, kc,
                                    preferred_element_type=jnp.float32)
                scores = scores / jnp.sqrt(cfg.head_dim).astype(jnp.float32)
                scores = jnp.where(valid[:, None] if cfg.decode_slots
                                   else valid[None, None], scores, -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1)
                out = jnp.einsum("bhij,bjhd->bihd",
                                 probs.astype(cfg.dtype), vc,
                                 preferred_element_type=jnp.float32
                                 ).astype(cfg.dtype)
        else:
            if rep > 1 and cfg.attention != "pallas":
                # Broadcast KV groups to full head count for backends that
                # expect equal head counts (dense / ring / ulysses). The
                # Pallas kernel is grouped-query-native: its index maps
                # stream the shared K/V per group, so the 4x repeat (two
                # activation-sized HBM tensors per layer plus the summed
                # dk/dv transpose in backward) never materializes.
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            # flash/ring/ulysses all take the block knobs (shared kernel
            # bodies); dense has no blocks
            if cfg.attn_block is not None and cfg.attention != "dense":
                attn_kwargs = dict(block_q=cfg.attn_block,
                                   block_k=cfg.attn_block)
            else:
                attn_kwargs = {}
            out = _attention_fn(cfg.attention)(q, k, v, causal=cfg.causal,
                                               **attn_kwargs)

        out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
        out = _dense_general(
            cfg.embed_dim, (Logical.HEADS, Logical.EMBED), cfg, "out",
            use_bias=cfg.use_bias, parallel="row",
        )(out)
        if cfg.dropout_rate > 0:
            out = nn.Dropout(cfg.dropout_rate)(out, deterministic=deterministic)
        return out if layer is None else (out, pool)


class MlpBlock(nn.Module):
    """Column-parallel wi (embed→mlp), row-parallel wo (mlp→embed): under TP
    rules XLA emits exactly Megatron's f/g psum pattern (parallel/tp.py)."""

    cfg: TransformerConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        deterministic = self.deterministic
        if cfg.activation == "swiglu":
            # Llama FFN: silu(x@W_gate) * (x@W_up), gate+up fused into one
            # [embed, 2, ffn] kernel (same MXU-utilization rationale as the
            # fused qkv projection; served as planes from a scanned stack:
            # `fused_kernel`); the stacked "2" dim is unsharded so
            # "mlp"→tensor still splits clean columns.
            kernel, eq = fused_kernel(self, "wi", 2, cfg.ffn_dim,
                                      (Logical.EMBED, None, Logical.MLP))
            gu = jnp.einsum(eq, x, kernel.astype(cfg.dtype),
                            _dot_general=_site_dot_general(
                                cfg, "column", jax.lax.dot_general))
            if cfg.use_bias:
                bias = self.param(
                    "wi_bias",
                    nn.with_logical_partitioning(
                        nn.initializers.zeros_init(), (None, Logical.MLP)),
                    (2, cfg.ffn_dim),
                    cfg.param_dtype,
                )
                gu = gu + bias.astype(cfg.dtype)
            h = nn.silu(gu[..., 0, :]) * gu[..., 1, :]
        else:
            h = _dense_general(cfg.ffn_dim, (Logical.EMBED, Logical.MLP), cfg,
                               "wi", use_bias=cfg.use_bias,
                               parallel="column")(x)
            h = nn.gelu(h, approximate=cfg.gelu_approximate)
        h = nn.with_logical_constraint(
            h, (Logical.BATCH, Logical.SEQ, Logical.MLP))
        out = _dense_general(cfg.embed_dim, (Logical.MLP, Logical.EMBED), cfg,
                             "wo", use_bias=cfg.use_bias,
                             parallel="row")(h)
        if cfg.dropout_rate > 0:
            out = nn.Dropout(cfg.dropout_rate)(out, deterministic=deterministic)
        return out


class UnitOffsetRMSNorm(nn.Module):
    """`x / sqrt(mean(x^2) + eps) * (1 + g)`, in float32: an RMSNorm whose
    stored gain `g` (``scale``) is about a unit offset
    (`cfg.norm_unit_offset`)."""

    epsilon: float
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        g = self.param(
            "scale", nn.with_logical_partitioning(
                nn.initializers.zeros_init(), (Logical.EMBED,)),
            (x.shape[-1],), self.param_dtype)
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + self.epsilon) * (
                1.0 + g.astype(jnp.float32))


def _layer_norm(cfg, name):
    """cfg.fused_norms=True: the custom_vjp norms (ops/norms.py) — fp32
    normalization math like the flax originals (same param trees, so
    checkpoints are unchanged), but bf16-input + row-stat residuals and a
    single-fusion backward instead of AD's saved fp32 intermediates (the
    r3 profile's ~64 ms/step of norm-backward reduce fusions on Llama-1B,
    BASELINE.md). Default: the flax modules, until the A/B is measured on
    the chip."""
    scale_init = nn.with_logical_partitioning(
        nn.initializers.ones_init(), (Logical.EMBED,))
    bias_init = nn.with_logical_partitioning(
        nn.initializers.zeros_init(), (Logical.EMBED,))
    if cfg.fused_norms:
        from pytorchdistributed_tpu.ops.norms import (
            FusedLayerNorm,
            FusedRMSNorm,
        )

        if cfg.norm == "rmsnorm":
            return FusedRMSNorm(epsilon=cfg.norm_eps,
                                param_dtype=cfg.param_dtype,
                                scale_init=scale_init, name=name)
        return FusedLayerNorm(epsilon=cfg.norm_eps,
                              param_dtype=cfg.param_dtype,
                              scale_init=scale_init, bias_init=bias_init,
                              name=name)
    if cfg.norm == "rmsnorm" and getattr(cfg, "norm_unit_offset", False):
        return UnitOffsetRMSNorm(epsilon=cfg.norm_eps,
                                 param_dtype=cfg.param_dtype, name=name)
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(
            epsilon=cfg.norm_eps,
            dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            scale_init=scale_init,
            name=name,
        )
    return nn.LayerNorm(
        epsilon=cfg.norm_eps,
        dtype=jnp.float32,  # normalize in fp32 regardless of compute dtype
        param_dtype=cfg.param_dtype,
        # a gain alone where the dialect has no bias anywhere (Cohere)
        use_bias=getattr(cfg, "use_bias", True),
        scale_init=scale_init,
        bias_init=bias_init,
        name=name,
    )


def rope_tables(seq_len: int, head_dim: int, theta: float,
                dtype=jnp.float32):
    """(cos, sin) tables ``[seq, head_dim/2]`` for rotary embeddings."""
    freqs = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                      / head_dim)
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rope(x, cos, sin):
    """Rotate ``x [b, s, h, d]`` by per-position angles (split-halves
    convention: pair dim i with dim i+d/2 — same rotation group as the
    interleaved convention, chosen because it lowers to two slices instead
    of a strided gather). Tables are ``[s, d/2]`` shared across rows, or
    ``[b, s, d/2]`` per-row (slot decode: each slot at its own offset)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    if cos.ndim == 3:
        c = cos[:, :, None, :].astype(x.dtype)
        s = sin[:, :, None, :].astype(x.dtype)
    else:
        c = cos[None, :, None, :].astype(x.dtype)
        s = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


class TransformerBlock(nn.Module):
    """Pre-LN block: x + Attn(LN(x)); x + MLP(LN(x)). Under
    ``block_form="parallel"`` one norm: x + Attn(LN(x)) + MLP(LN(x))."""

    cfg: TransformerConfig
    deterministic: bool = True
    # None = cfg-driven (every block is MoE when moe_experts > 0); the
    # unrolled stack passes the per-layer moe_every interleaving decision.
    use_moe: bool | None = None
    # this layer's kind in a period (SelfAttention's `rope`, `window`;
    # `mixer` "mamba": models/ssm.py's mixer in attention's place)
    rope: bool | None = None
    window: int = 0
    mixer: str = "attention"

    def _sow_diagnostics(self, x):
        """In-graph block-boundary health stats (ISSUE 6): sow
        RMS/absmax/non-finite-count of the block OUTPUT — and, under
        quantized training, the int8 clip fraction of the activations
        entering the next block's matmuls — into the "diagnostics"
        collection. Gated entirely on the collection being MUTABLE in
        this apply (the Trainer's diagnostics knob passes it through the
        losses): when it isn't, nothing is traced, so a diagnostics-off
        program is byte-identical HLO to one that predates the knob
        (pinned by tests/test_compiled_invariants.py). Under nn.scan the
        sown vectors stack along the layer axis into the [L, 3] table
        telemetry/diagnostics.py collects."""
        if self.is_initializing() or not self.is_mutable_collection(
                "diagnostics"):
            return
        from pytorchdistributed_tpu.telemetry.diagnostics import (
            activation_stat_vec,
        )

        self.sow("diagnostics", "out_stats", activation_stat_vec(x))
        if self.cfg.quant != "none":
            from pytorchdistributed_tpu.ops.quant import saturation_fraction

            self.sow("diagnostics", "int8_sat",
                     saturation_fraction(x, axis=-1))

    @nn.compact
    def __call__(self, x, paging=None, pool=None, layer=None, banks=None):
        """``paging``, ``pool``, ``layer``: the paged stack's per-slot
        state, and the scanned stack's KV pool with this block's index in
        it (SelfAttention); with a pool the call returns ``(x, pool)``.
        ``banks``: this block's part of `TransformerStack._expert_banks`
        with the scan's step, where the stack hands them."""
        cfg = self.cfg
        x = nn.with_logical_constraint(
            x, (Logical.BATCH, Logical.SEQ, Logical.EMBED))

        def norm(tag, v):  # named so remat policies can keep it (bf16)
            return jax.ad_checkpoint.checkpoint_name(
                _layer_norm(cfg, tag)(v).astype(cfg.dtype), "norm_out")

        def ffn(h, route=None):
            moe = cfg.moe_experts > 0 and (self.use_moe is None
                                           or self.use_moe)
            if moe:
                from pytorchdistributed_tpu.models.moe import SwitchMoE

                return SwitchMoE(cfg, self.deterministic, name="moe")(h)
            if cfg.router_experts:
                from pytorchdistributed_tpu.models.moe import DroplessMoE

                out, counted = DroplessMoE(cfg, name="moe")(
                    h, None if paging is None else paging["live"], route,
                    banks and (banks[0]["moe"], banks[1]))
                count(counted)
                return out
            return MlpBlock(cfg, self.deterministic, name="mlp")(h)

        def count(counted):
            """What a sublayer counted, onto the vector that rides the
            scanned stack's carry beside the pools (`COUNTS`)."""
            nonlocal pool
            if pool is not None and COUNTS in pool:
                pool = dict(pool)
                pool[COUNTS] = pool[COUNTS] + jnp.stack([
                    jnp.asarray(counted.get(n, 0.0), jnp.float32)
                    for n in cfg.counter_names])

        if self.mixer == "mamba":
            from pytorchdistributed_tpu.models.ssm import MambaMixer

            attn_module = MambaMixer(cfg, name="mamba")
        else:
            attn_module = SelfAttention(cfg, self.deterministic, self.rope,
                                        self.window, name="attn")

        def attn(h):
            nonlocal pool
            out = attn_module(h, paging, pool, layer)
            if layer is not None:
                out, pool = out
            return out

        if cfg.norm_position == "post":
            # original-BERT residual order: LN AFTER each sublayer's add
            x = norm("ln1", x + attn(x))
            x = norm("ln2", x + ffn(x))
        elif cfg.block_form == "parallel":
            # one norm, attention and the feed-forward side by side
            # (Cohere's use_parallel_block): both read `u`, a router too
            u = norm("ln1", x)
            x = x + attn(u) + ffn(u)
        elif cfg.router_experts and cfg.router_input == "attn":
            # the router placed before attention: it reads what attention
            # reads, the experts the normed stream after it
            u = norm("ln1", x)
            x = x + attn(u)
            x = x + ffn(norm("ln2", x), u)
        else:
            x = x + attn(norm("ln1", x))
            x = x + ffn(norm("ln2", x))
        self._sow_diagnostics(x)
        x = nn.with_logical_constraint(
            x, (Logical.BATCH, Logical.SEQ, Logical.EMBED))
        return x if layer is None else (x, pool)


#: the key under which what the blocks count rides the scanned stack's
#: carry, beside the pools: one vector, `TransformerConfig.counter_names`
COUNTS = "counts"


class PeriodBlock(nn.Module):
    """The scanned body where the layers come in kinds
    (`TransformerConfig.period`): one whole period, a `TransformerBlock`
    a layer with its own ``rope`` and ``window``, or its mamba mixer
    (``layer_<j>``). Layer ``j`` of period ``p`` keeps its rows (a mamba
    layer: its states) at index ``p * n + r`` of its kind's pool, ``n``
    the kind's layers a period and ``r`` its rank among them."""

    cfg: TransformerConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, x, paging, pool, period, banks=None):
        cfg = self.cfg
        kinds = [cfg.layer_kind(e) for e in cfg.period]
        rank = dict.fromkeys(kinds, 0)
        for j, (entry, kind) in enumerate(zip(cfg.period, kinds)):
            at = dict(mixer="mamba") if kind == "mamba" else dict(
                rope=bool(entry[0]), window=entry[1])
            x, pool = TransformerBlock(
                cfg, self.deterministic, name=f"layer_{j}", **at)(
                    x, paging, pool,
                    period * kinds.count(kind) + rank[kind],
                    banks and (banks[0][f"layer_{j}"], banks[1]))
            rank[kind] += 1
        return x, pool


def check_pipeline_decomposition(cfg: TransformerConfig) -> int:
    """Shared pipeline_parts validation (GPT-2/Llama/BERT/ViT): returns the
    stage count after checking the scanned layout divides into it."""
    p = cfg.pipeline_stages
    if cfg.num_layers % p:
        raise ValueError(f"num_layers {cfg.num_layers} not divisible by "
                         f"pipeline_stages {p}")
    if not cfg.scan_layers:
        raise ValueError("pipeline_parts requires scan_layers=True")
    return p


def stack_to_stages(blocks, cfg: TransformerConfig):
    """[L, ...]-stacked block params → [P, L/P, ...] stage groups
    (contiguous layers per stage, matching the stage-axis sharding)."""
    p = cfg.pipeline_stages
    return jax.tree.map(
        lambda a: a.reshape(p, cfg.num_layers // p, *a.shape[1:]), blocks)


def stages_to_stack(stage_grads, cfg: TransformerConfig):
    """Inverse of stack_to_stages for the gradient merge."""
    return jax.tree.map(
        lambda a: a.reshape(cfg.num_layers, *a.shape[2:]), stage_grads)


def make_stage_apply(cfg: TransformerConfig, *, aux: bool = False):
    """Build the pipeline stage body shared by the GPipe apply path
    (TransformerStack._pipelined) and the models' 1F1B ``pipeline_parts``:
    apply ``num_layers/pipeline_stages`` TransformerBlocks from a
    stage-stacked param leaf.

    The returned ``stage_apply(stage_leaf, h, key=None)``:
      * with ``key`` (the schedule's ``stage_microbatch_key``), folds the
        layer index on top and runs the blocks stochastic — dropout streams
        are unique per (stage, micro-batch, layer);
      * with ``aux=True`` returns ``(h, aux_sum)`` where aux_sum collects
        the Switch-MoE load-balance values the blocks sow — raw
        ``block.apply`` outside the module system would otherwise drop them
        silently (a collapsing router with no warning).
    """
    per = cfg.num_layers // cfg.pipeline_stages
    det_block = TransformerBlock(cfg, deterministic=True)
    sto_block = TransformerBlock(cfg, deterministic=False)

    def stage_apply(stage_leaf, h, key=None):
        block = det_block if key is None else sto_block

        def rngs_for(j):
            return (None if key is None
                    else {"dropout": jax.random.fold_in(key, j)})

        if aux:
            from pytorchdistributed_tpu.parallel.pipeline import _to_varying

            def layer(carry, xs):
                h, aux_acc = carry
                lp, j = xs
                h, mods = block.apply({"params": lp}, h, rngs=rngs_for(j),
                                      mutable=["losses"])
                from pytorchdistributed_tpu.training.losses import (
                    pipeline_aux_fold,
                )

                aux_acc = aux_acc + pipeline_aux_fold(mods.get("losses", {}))
                return (h, aux_acc), None

            (h, aux_sum), _ = jax.lax.scan(
                layer, (h, _to_varying(jnp.zeros((), jnp.float32))),
                (stage_leaf, jnp.arange(per)))
            return h, aux_sum

        def layer(h, xs):
            lp, j = xs
            return block.apply({"params": lp}, h, rngs=rngs_for(j)), None

        h, _ = jax.lax.scan(layer, h, (stage_leaf, jnp.arange(per)))
        return h

    return stage_apply


class TransformerStack(nn.Module):
    """num_layers blocks, optionally folded into one `nn.scan` whose carry is
    the activations. The scanned parameter axis gets logical name "stage"
    (→ mesh axis "pipe"), which is what pipeline parallelism shards."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True):
        cfg = self.cfg
        if cfg.pipeline_stages > 1 and not self.is_initializing():
            return self._pipelined(x, deterministic)
        block = TransformerBlock
        if cfg.remat:
            # recompute block activations in backward (GPipe's "time for
            # space", reference 03_model_parallel.ipynb:637-643); the
            # policy selects *selective* recomputation (keep matmul
            # outputs, redo cheap elementwise) vs full-block recompute
            block = nn.remat(block, prevent_cse=not cfg.scan_layers,
                             policy=checkpoint_policy(cfg.remat_policy))
        paged = bool(cfg.decode and cfg.kv_block_size)
        if paged:
            # what every layer of a paged stack reads alike, held once:
            # each slot's position and block table and, under per-slot
            # limits (ISSUE 15), its sink/window — "cache" leaves the
            # serving engine stamps from host state on every call
            slots = cfg.decode_slots
            state = {
                "index": self.variable(
                    "cache", "index",
                    lambda: jnp.zeros((slots,), jnp.int32)),
                # one block table a kind of cache (`block_table` alone,
                # unless the model keeps two pools)
                **{kind.table: self.variable(
                       "cache", kind.table, jnp.zeros,
                       (slots, kind.pages(cfg.kv_pages)), jnp.int32)
                   for kind in cfg.cache_kinds if kind.table}}
            if cfg.state_leaves or cfg.counter_names:
                # where each slot's tokens of this call end (exclusive):
                # a recurrent state takes no step past it, and what the
                # layers count on the device counts no position past it
                state["stop"] = self.variable(
                    "cache", "stop", lambda: jnp.zeros((slots,), jnp.int32))
            if cfg.per_slot_kv_limits and cfg.kv_window_tokens:
                state["kv_sinks"] = self.variable(
                    "cache", "kv_sinks",
                    lambda: jnp.full((slots,), cfg.kv_sink_tokens,
                                     jnp.int32))
                state["kv_windows"] = self.variable(
                    "cache", "kv_windows",
                    lambda: jnp.full((slots,), cfg.kv_window_tokens,
                                     jnp.int32))
            paging = {name: var.value for name, var in state.items()}
            if "stop" in paging:
                # the call's positions that are a stream's own, which the
                # layers' counters count: a live slot's in a tick (a free
                # one ticks along at length 0, computed and never
                # counted), those before the prompt's end in a chunk
                paging["live"] = (paging["index"][:, None]
                                  + jnp.arange(x.shape[1])
                                  < paging["stop"][:, None])
        if cfg.scan_layers:
            scan = functools.partial(
                nn.scan,
                variable_axes={"params": 0, "losses": 0, "cache": 0,
                               "diagnostics": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: Logical.STAGE})
            if paged:
                # the paged pool is the loop's CARRY, one [num_layers,
                # ...] array a leaf, held here and not in the scanned
                # blocks: as scanned input and output every layer's pool
                # would be sliced out of one stack and written into a
                # second, and the whole stack copied back after the loop
                own = {name: self.variable(
                           "cache", name, jnp.zeros,
                           (cfg.pool_layers(name),) + shape, dtype)
                       for name, (shape, dtype)
                       in {**cfg.kv_pool_leaves,
                           **cfg.state_leaves}.items()}
                carried = {name: var.value for name, var in own.items()}
                counts = None
                if cfg.eva_window:
                    # what the layers count of the masks they attend
                    # under rides the carry beside the pools
                    from pytorchdistributed_tpu.models import eva

                    counts = eva.COUNTS
                elif cfg.counter_names:
                    counts = COUNTS
                if counts:
                    carried[counts] = jnp.zeros(
                        (len(cfg.counter_names),), jnp.float32)
                if cfg.period:
                    # the body is a whole period of layer kinds
                    block = PeriodBlock
                    scan = functools.partial(
                        scan, length=cfg.num_layers // len(cfg.period))
                (x, pool), _ = scan(
                    lambda mdl, carry, paging, banks, step: (
                        mdl(carry[0], paging, carry[1], step,
                            banks and (banks, step)), None),
                    in_axes=(nn.broadcast, nn.broadcast, 0),
                )(block(cfg, deterministic, name="block"),
                  (x, carried), paging, self._expert_banks(),
                  jnp.arange(cfg.num_layers // max(1, len(cfg.period))))
                if counts and self.is_mutable_collection("counters"):
                    self.variable("counters", "tick", jnp.zeros,
                                  (len(cfg.counter_names),)).value = pool[
                                      counts]
                if not self.is_initializing():
                    for name, var in own.items():
                        var.value = pool[name]
            else:
                x, _ = scan(lambda mdl, carry, _: (mdl(carry), None))(
                    block(cfg, deterministic, name="block"), x, None)
        else:
            interleave = cfg.moe_experts > 0 and cfg.moe_every > 1
            for i in range(cfg.num_layers):
                kw = ({"use_moe": (i + 1) % cfg.moe_every == 0}
                      if interleave else {})
                layer = block(cfg, deterministic, name=f"block_{i}", **kw)
                x = layer(x, paging) if paged else layer(x)
        if paged and not self.is_initializing():
            state["index"].value = paging["index"] + x.shape[1]
        return x

    def _expert_banks(self):
        """The experts' banks (`models/moe.py:BANKS`) of every step of the
        scan, as they lie in this stack's parameters: each ``[steps,
        held, ...]`` leaf whole, as ``[steps * held, ...]`` (a bitcast),
        under its names below ``block``. Handed to the scanned body as a
        loop invariant beside ``paging``, `DroplessMoE`'s grouped
        products read step ``p``'s experts from group ``p * held`` on and
        the scan's own slice of the leaf is dead: `lax.ragged_dot` on
        that slice has it copied out of the stack first, a layer's
        experts before every product (ISSUE 37). None, and the body
        slices as before, where there are no such experts, while the
        parameters are made, where the banks are kept in another type
        than the products multiply in (the cast would be a copy of the
        whole stack), and under ``quant``, which rounds the weights a
        bank at a time."""
        from pytorchdistributed_tpu.models.moe import BANKS, stack_hands_banks

        cfg = self.cfg
        if not cfg.router_experts or self.is_initializing():
            return None

        def banks_of(tree):
            if "moe" in tree:
                return {"moe": {
                    name: tree["moe"][name].reshape(
                        (-1,) + tree["moe"][name].shape[2:])
                    for name in BANKS}}
            return {name: banks_of(sub) for name, sub in tree.items()}

        banks = banks_of(self.variables["params"]["block"])
        return banks if stack_hands_banks(cfg, banks) else None

    def _pipelined(self, x, deterministic: bool):
        """Apply-path GPipe: reuse the layer-stacked params the init-path
        nn.scan created ([L, ...] leaves, logical axis "stage" → mesh axis
        "pipe") and drive them with the shard_map pipeline schedule
        (parallel/pipeline.py) instead of the sequential scan. Dropout rides
        as a per-(stage, micro-batch, layer) key stream; the Switch-MoE aux
        loss is collected from the schedule and re-sown so the moe loss fn
        sees it exactly like the sequential stack's."""
        from pytorchdistributed_tpu.parallel.pipeline import gpipe_spmd

        cfg = self.cfg
        p = cfg.pipeline_stages
        if not cfg.scan_layers:
            raise ValueError("pipeline_stages > 1 requires scan_layers=True")
        if cfg.num_layers % p != 0:
            raise ValueError(
                f"num_layers {cfg.num_layers} not divisible by "
                f"pipeline_stages {p}")
        stacked = self.get_variable("params", "block")
        # [L, ...] -> [P, L/P, ...]: contiguous layer groups become stages,
        # matching the existing stage-axis sharding layout.
        stage_params = jax.tree.map(
            lambda a: a.reshape(p, cfg.num_layers // p, *a.shape[1:]),
            stacked)
        train_dropout = cfg.dropout_rate > 0 and not deterministic
        dropout_rng = self.make_rng("dropout") if train_dropout else None
        collect_aux = cfg.moe_experts > 0
        out = gpipe_spmd(make_stage_apply(cfg, aux=collect_aux),
                         stage_params, x,
                         num_microbatches=cfg.pipeline_microbatches,
                         remat=cfg.remat, remat_policy=cfg.remat_policy,
                         dropout_rng=dropout_rng, collect_aux=collect_aux)
        if collect_aux:
            out, aux = out
            # same convention as the sequential scan's [L]-sow consumed by
            # losses.moe_token_cross_entropy_loss: a mean over layers
            # (gpipe_spmd already averaged over micro-batches); sow is a
            # silent no-op when "losses" isn't mutable (plain CE loss)
            self.sow("losses", "moe_aux", aux / cfg.num_layers)
        return out


class LMHead(nn.Module):
    """Untied logit projection, setup-style so the kernel is an attribute —
    the fused chunked-CE loss path (ops/fused_ce.py) reads it directly
    instead of materializing logits. Param tree: ``lm_head/kernel``."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        self.kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02),
                (Logical.EMBED, Logical.VOCAB)),
            (cfg.embed_dim, cfg.vocab_size),
            cfg.param_dtype,
        )

    def __call__(self, x):
        x = x.astype(self.cfg.dtype)
        kernel = self.kernel.astype(self.cfg.dtype)
        dg = _cfg_dot_general(self.cfg)
        if self.cfg.fp32_logits:
            # products in `dtype`, summed and left in float32, so that a
            # near-tie between logits is not decided by rounding the sum
            return (dg or jax.lax.dot_general)(
                x, kernel, (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        if dg is None:
            return x @ kernel
        return dg(x, kernel, (((x.ndim - 1,), (0,)), ((), ())))


class ProposalHeads(nn.Module):
    """Medusa-style multi-token proposal heads (cfg.spec_heads > 0, ISSUE
    16): head j maps the final hidden state x to ``x + silu(W_j x)`` with
    W_j (and its bias) ZERO-initialized — silu(0) == 0, so every head's
    hidden state starts exactly equal to x and its logits (through the
    shared tied/untied projection the model owns) start exactly equal to
    the base next-token head's; silu'(0) == 0.5 keeps gradients flowing,
    so distillation (training/distill.py) specializes each head to its
    own offset from a sane start. Param tree: ``heads/head_{j}/...``."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        """[..., embed] -> [..., spec_heads, embed] per-head hidden
        states, ready for the model's shared logit projection."""
        cfg = self.cfg
        x = x.astype(cfg.dtype)
        outs = []
        for j in range(cfg.spec_heads):
            r = nn.Dense(
                cfg.embed_dim, use_bias=True, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    # (None, EMBED), not (EMBED, EMBED): logical axis
                    # names may not repeat within one array
                    nn.initializers.zeros, (None, Logical.EMBED)),
                bias_init=nn.initializers.zeros,
                name=f"head_{j}")(x)
            outs.append(x + nn.silu(r))
        return jnp.stack(outs, axis=-2)


class Embedder(nn.Module):
    """Token + learned positional embeddings; `attend` gives the tied logit
    projection (GPT-2 weight tying)."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        self.tok = nn.Embed(
            cfg.vocab_size, cfg.embed_dim,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02),
                (Logical.VOCAB, Logical.EMBED)),
            name="tok",
        )
        if not cfg.rope:  # RoPE models carry position in q/k rotation
            self.pos = self.param(
                "pos",
                nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.02),
                    (None, Logical.EMBED)),
                (cfg.max_seq_len, cfg.embed_dim),
                cfg.param_dtype,
            )
            if cfg.decode:
                self.pos_index = self.variable(
                    "cache", "pos_index",
                    lambda: jnp.zeros(
                        (cfg.decode_slots,) if cfg.decode_slots else (),
                        jnp.int32))

    def __call__(self, tokens):
        seq_len = tokens.shape[1]
        x = self.tok(tokens)
        if self.cfg.rope:
            return x
        if self.cfg.decode:
            idx = self.pos_index.value
            if self.cfg.decode_slots:
                # per-row positions (slot decode): gather [b, s, embed]
                p = self.pos[idx[:, None] + jnp.arange(seq_len)]
            else:
                p = jax.lax.dynamic_slice_in_dim(self.pos, idx, seq_len)
            if not self.is_initializing():
                self.pos_index.value = idx + seq_len
            return x + p.astype(self.cfg.dtype)
        return x + self.pos[:seq_len].astype(self.cfg.dtype)

    def attend(self, x):
        x = x.astype(self.cfg.dtype)
        dg = _cfg_dot_general(self.cfg)
        if self.cfg.fp32_logits:
            # as `LMHead`'s: products in `dtype`, summed in float32
            emb = self.tok.embedding.astype(self.cfg.dtype)
            return (dg or jax.lax.dot_general)(
                x, emb, (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        if dg is None:
            return self.tok.attend(x)
        # the tied logit projection [.., embed] x [vocab, embed]ᵀ through
        # the quantized contraction (same math as Embed.attend)
        emb = self.tok.embedding.astype(self.cfg.dtype)
        return dg(x, emb, (((x.ndim - 1,), (1,)), ((), ())))
