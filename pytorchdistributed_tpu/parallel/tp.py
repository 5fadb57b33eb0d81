"""Tensor parallelism — Megatron-style sharding as logical-axis rules.

The reference names Megatron only for its pipeline schedule
(reference 03_model_parallel.ipynb:699); intra-layer tensor parallelism is
absent there but required for framework completeness (SURVEY.md §2c). On TPU
it is NOT a wrapper class or hand-written f/g collectives: model parameters
carry *logical* axis names (via `nn.with_logical_partitioning`), and a rule
table maps logical axes onto mesh axes. XLA then derives the Megatron
communication pattern itself:

  * column-parallel Dense  = kernel ("embed", "mlp"→tensor): output stays
    sharded, no collective;
  * row-parallel Dense     = kernel ("mlp"→tensor, "embed"): XLA inserts the
    activation psum that Megatron's `g` operator performs;
  * sharded attention heads = ("embed", "heads"→tensor, "kv").

The same logical names serve FSDP (shard "embed" on the fsdp axis) and
sequence parallelism (activations' "seq" on the seq axis), so one model
definition supports every strategy combination — the design stance of
SURVEY.md §7 (strategies are PartitionSpec choices, not model rewrites).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
from jax.sharding import Mesh

from pytorchdistributed_tpu.runtime.mesh import Axis


class Logical:
    """Canonical logical axis names used by the model zoo."""

    BATCH = "batch"
    SEQ = "seq"          # activation sequence dim (context parallelism)
    EMBED = "embed"      # model/hidden dim
    MLP = "mlp"          # FFN intermediate dim (Megatron column dim)
    HEADS = "heads"      # attention heads (Megatron attention shard dim)
    KV = "kv"            # per-head dim (never sharded)
    VOCAB = "vocab"      # embedding/logit dim
    EXPERT = "expert"    # MoE expert dim
    EGROUP = "egroup"    # MoE routing-group dim (models/moe.py grouped
    #                      tokens: one group per data×fsdp×expert shard)
    CONV_IN = "conv_in"
    CONV_OUT = "conv_out"
    STAGE = "stage"      # pipeline stage dim (scanned-layer models)


# rule tables: logical axis -> mesh axis (or None = replicated). Written as
# tuple-of-pairs, the format `flax.linen.logical_axis_rules` accepts.
_COMMON_ACTIVATION_RULES = (
    (Logical.BATCH, (Axis.DATA, Axis.FSDP)),
    (Logical.SEQ, Axis.SEQ),
    (Logical.STAGE, Axis.PIPE),
    # MoE routing groups tile every batch-ish axis INCLUDING "expert":
    # the layout in which grouped dispatch is a pure permutation (a
    # literal all_to_all), and a free slice of the (data, fsdp)-sharded
    # tokens since they were replicated over the expert axis.
    (Logical.EGROUP, (Axis.DATA, Axis.FSDP, Axis.EXPERT)),
)

_PARAM_RULES = {
    # DDP: params fully replicated — except stacked expert kernels,
    # which shard over "expert" under EVERY strategy (a dp×expert mesh
    # is the canonical MoE training mesh; on an expert-less mesh the
    # rule is a no-op).
    "dp": (
        (Logical.EXPERT, Axis.EXPERT),
    ),
    # ZeRO-3: shard the embed dim of every large param over "fsdp".
    "fsdp": (
        (Logical.EMBED, Axis.FSDP),
        (Logical.VOCAB, Axis.FSDP),
        (Logical.CONV_OUT, Axis.FSDP),
        (Logical.EXPERT, Axis.EXPERT),
    ),
    # Megatron TP: FFN columns, attention heads and vocab over "tensor".
    "tp": (
        (Logical.MLP, Axis.TENSOR),
        (Logical.HEADS, Axis.TENSOR),
        (Logical.VOCAB, Axis.TENSOR),
        (Logical.EXPERT, Axis.EXPERT),
    ),
    # 2D: TP within, FSDP across — the large-model default.
    "tp_fsdp": (
        (Logical.MLP, Axis.TENSOR),
        (Logical.HEADS, Axis.TENSOR),
        (Logical.VOCAB, Axis.TENSOR),
        (Logical.EXPERT, Axis.EXPERT),
        (Logical.EMBED, Axis.FSDP),
        (Logical.CONV_OUT, Axis.FSDP),
    ),
}
_PARAM_RULES["ddp"] = _PARAM_RULES["dp"]
_PARAM_RULES["zero3"] = _PARAM_RULES["fsdp"]
_PARAM_RULES["2d"] = _PARAM_RULES["tp_fsdp"]


def logical_rules(strategy: str):
    """Full rule table (params + activations) for a named strategy."""
    if strategy not in _PARAM_RULES:
        raise ValueError(
            f"unknown strategy {strategy!r}; one of {sorted(_PARAM_RULES)}"
        )
    return _PARAM_RULES[strategy] + _COMMON_ACTIVATION_RULES


def has_logical_annotations(abstract_params) -> bool:
    """True if the (possibly abstract) param tree carries flax Partitioned
    boxes — i.e. the model declared logical axes."""
    found = False

    def visit(leaf):
        nonlocal found
        if isinstance(leaf, nn.Partitioned):
            found = True
        return leaf

    jax.tree.map(visit, abstract_params,
                 is_leaf=lambda x: isinstance(x, nn.Partitioned))
    return found


def logical_shardings(abstract_params, mesh: Mesh, strategy: str):
    """NamedShardings for a boxed (logically-annotated) param tree.

    A dim its assigned mesh axes do not divide is left replicated: jit
    refuses uneven argument shardings, and GPT-2's published vocabulary
    (50,257, odd) meets that under every ``vocab → tensor`` rule. The
    embedding/LM-head then replicates over that axis — memory and head
    FLOPs a padded vocabulary would save, which is a model change."""
    specs = nn.get_partition_spec(abstract_params)
    shardings = nn.logical_to_mesh_sharding(
        specs, mesh, logical_rules(strategy))

    def extent(axes) -> int:  # devices behind one PartitionSpec entry
        axes = (axes,) if isinstance(axes, str) else axes or ()
        return math.prod(mesh.shape[a] for a in axes)

    def even(leaf, sharding):
        return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
            *(axes if dim % extent(axes) == 0 else None
              for dim, axes in zip(leaf.shape, sharding.spec))))

    return jax.tree.map(even, nn.meta.unbox(abstract_params), shardings)


