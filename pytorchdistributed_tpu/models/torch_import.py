"""PyTorch → TPU-framework weight import (GPT-2, Llama, BERT, ViT).

The migration story for users of the reference stack: take the
``state_dict`` of a torch/HuggingFace model — the ecosystem the reference
trains in — and load it into this framework's param trees, so a
torch-pretrained checkpoint serves, fine-tunes, and shards here without
retraining. Pure tensor re-layout on host numpy: no torch autograd, no
device work, and transformers is only needed by the tests.

Conventions handled:
  * HF GPT-2 stores ``Conv1D`` weights ``[in, out]`` (y = x@W + b) — no
    transpose; Llama stores ``nn.Linear`` weights ``[out, in]`` —
    transposed on import.
  * Our fused stacks: GPT-2 ``qkv_kernel [E, 3, H·D]`` from c_attn's
    contiguous q|k|v columns; Llama ``kv_kernel [E, 2, KV·D]`` and
    swiglu ``wi_kernel [E, 2, F]`` (index 0 = gate/silu, 1 = up — the
    convention in models/transformer.py MlpBlock). This is the checkpoint
    layout, the one `init` and the Trainer use too; the serving engine
    re-lays its own copy of a scanned stack's fused kernels as planes
    (serving/weights.py:served), so an imported tree serves as it is.
  * ``scan_layers=True`` trees stack the per-layer leaves on a leading
    layer axis (``h.block``); unrolled trees use ``h.block_{i}``.
  * Architecture fidelity comes from the family presets: ``norm_eps``
    (gpt2 1e-5, llama 1e-5, bert/vit 1e-12), BERT's post-LN order and
    exact GELU, ViT's exact GELU — logit-level parity vs the torch
    forward is asserted in tests/test_torch_import.py.

Tensors are converted via ``.detach().cpu().numpy()`` when torch tensors
are passed; plain numpy arrays work too (e.g. from a safetensors reader).
"""

from __future__ import annotations

import numpy as np


def _np(t) -> np.ndarray:
    if hasattr(t, "detach"):  # torch.Tensor without importing torch
        t = t.detach().cpu().numpy()
    return np.asarray(t, np.float32)


def _lin(sd, key) -> np.ndarray:
    """torch ``nn.Linear`` weight ``[out, in]`` → our kernel ``[in, out]``
    (HF GPT-2's Conv1D is already ``[in, out]`` and skips this)."""
    return _np(sd[key]).T


def _check_positions(pos: np.ndarray, cfg) -> np.ndarray:
    if pos.shape[0] < cfg.max_seq_len:
        raise ValueError(
            f"checkpoint has {pos.shape[0]} positions < cfg.max_seq_len "
            f"{cfg.max_seq_len}")
    return pos[: cfg.max_seq_len]


def _finish(tree: dict, cfg) -> dict:
    """Cast every leaf to cfg.param_dtype so the imported tree matches a
    model-initialized one exactly (a bf16-param config must not silently
    double its footprint with fp32 leaves)."""
    import jax

    return jax.tree.map(lambda a: a.astype(cfg.param_dtype), tree)


def _hf_encoder_block(sd, p: str, attn: str, ln1: str, ln2: str) -> dict:
    """One HF post-2018-encoder layer (BERT/ViT share the shape): stacked
    q/k/v Linears under ``attn`` prefix, dense out/wi/wo, two LayerNorms
    named ``ln1``/``ln2`` relative to ``p``."""
    qkv_w = np.stack([_lin(sd, attn + f"{n}.weight")
                      for n in ("query", "key", "value")], axis=1)
    qkv_b = np.stack([_np(sd[attn + f"{n}.bias"])
                      for n in ("query", "key", "value")])
    return {
        "ln1": {"scale": _np(sd[p + ln1 + ".weight"]),
                "bias": _np(sd[p + ln1 + ".bias"])},
        "ln2": {"scale": _np(sd[p + ln2 + ".weight"]),
                "bias": _np(sd[p + ln2 + ".bias"])},
        "attn": {
            "qkv_kernel": qkv_w,            # [E, 3, E]
            "qkv_bias": qkv_b,              # [3, E]
            "out": {"kernel": _lin(sd, p + "attention.output.dense.weight"),
                    "bias": _np(sd[p + "attention.output.dense.bias"])},
        },
        "mlp": {
            "wi": {"kernel": _lin(sd, p + "intermediate.dense.weight"),
                   "bias": _np(sd[p + "intermediate.dense.bias"])},
            "wo": {"kernel": _lin(sd, p + "output.dense.weight"),
                   "bias": _np(sd[p + "output.dense.bias"])},
        },
    }


def _stack_blocks(blocks: list[dict], scan_layers: bool) -> dict:
    """Per-layer param subtrees → the stack's tree: stacked on a leading
    layer axis under "block" (scan_layers) or "block_{i}" children."""
    if not scan_layers:
        return {f"block_{i}": b for i, b in enumerate(blocks)}
    import jax

    return {"block": jax.tree.map(lambda *ls: np.stack(ls), *blocks)}


def gpt2_params_from_torch(state_dict, cfg) -> dict:
    """HF ``GPT2LMHeadModel.state_dict()`` → ``{"params": ...}`` for
    models/gpt2.GPT2 built with ``gpt2_config(...)`` (tied embeddings).

    Accepts keys with or without the ``transformer.`` prefix. ``wpe`` may
    be longer than ``cfg.max_seq_len`` (sliced); shorter raises.
    """
    sd = {k.removeprefix("transformer."): v for k, v in state_dict.items()}
    e = cfg.embed_dim
    if not cfg.tie_embeddings:
        raise ValueError("GPT-2 import expects tie_embeddings=True "
                         "(the released models tie wte and lm_head)")
    wpe = _check_positions(_np(sd["wpe.weight"]), cfg)

    def block(i):
        p = f"h.{i}."
        qkv_w = _np(sd[p + "attn.c_attn.weight"])       # [E, 3E], x@W
        qkv_b = _np(sd[p + "attn.c_attn.bias"])         # [3E]
        return {
            "ln1": {"scale": _np(sd[p + "ln_1.weight"]),
                    "bias": _np(sd[p + "ln_1.bias"])},
            "ln2": {"scale": _np(sd[p + "ln_2.weight"]),
                    "bias": _np(sd[p + "ln_2.bias"])},
            "attn": {
                "qkv_kernel": qkv_w.reshape(e, 3, e),
                "qkv_bias": qkv_b.reshape(3, e),
                "out": {"kernel": _np(sd[p + "attn.c_proj.weight"]),
                        "bias": _np(sd[p + "attn.c_proj.bias"])},
            },
            "mlp": {
                "wi": {"kernel": _np(sd[p + "mlp.c_fc.weight"]),
                       "bias": _np(sd[p + "mlp.c_fc.bias"])},
                "wo": {"kernel": _np(sd[p + "mlp.c_proj.weight"]),
                       "bias": _np(sd[p + "mlp.c_proj.bias"])},
            },
        }

    return _finish({"params": {
        "embed": {"tok": {"embedding": _np(sd["wte.weight"])},
                  "pos": wpe},
        "h": _stack_blocks([block(i) for i in range(cfg.num_layers)],
                           cfg.scan_layers),
        "ln_f": {"scale": _np(sd["ln_f.weight"]),
                 "bias": _np(sd["ln_f.bias"])},
    }}, cfg)


def bert_params_from_torch(state_dict, cfg) -> dict:
    """HF ``BertForMaskedLM.state_dict()`` → ``{"params": ...}`` for
    models/bert.BertMLM built with ``bert_config(...)`` (post-LN blocks,
    exact GELU, eps 1e-12 — the preset pins all three).

    Single-segment convention: HF adds ``token_type_embeddings[0]`` to
    every position when ``token_type_ids`` are all zero (the MLM batch
    contract here has no segment ids), so that row folds into the
    position table. The pooler is dropped (MLM never reads it)."""
    sd = state_dict
    emb = "bert.embeddings."
    pos = _check_positions(_np(sd[emb + "position_embeddings.weight"]), cfg)
    pos = pos + _np(sd[emb + "token_type_embeddings.weight"])[0]

    def lin(key):
        return _lin(sd, key)

    def block(i):
        p = f"bert.encoder.layer.{i}."
        return _hf_encoder_block(sd, p, p + "attention.self.",
                                 ln1="attention.output.LayerNorm",
                                 ln2="output.LayerNorm")

    t = "cls.predictions.transform."
    return _finish({"params": {
        "embed": {
            "tok": {"embedding": _np(sd[emb + "word_embeddings.weight"])},
            "pos": pos},
        "ln_embed": {"scale": _np(sd[emb + "LayerNorm.weight"]),
                     "bias": _np(sd[emb + "LayerNorm.bias"])},
        "encoder": _stack_blocks(
            [block(i) for i in range(cfg.num_layers)], cfg.scan_layers),
        "mlm_dense": {"kernel": lin(t + "dense.weight"),
                      "bias": _np(sd[t + "dense.bias"])},
        "mlm_ln": {"scale": _np(sd[t + "LayerNorm.weight"]),
                   "bias": _np(sd[t + "LayerNorm.bias"])},
        "mlm_bias": _np(sd["cls.predictions.bias"]),
    }}, cfg)


def vit_params_from_torch(state_dict, cfg) -> dict:
    """HF ``ViTForImageClassification.state_dict()`` → ``{"params": ...}``
    for models/vit.ViT built with ``vit_config(...)``. Images here are
    NHWC (the TPU-native layout) — callers feeding torch-preprocessed
    NCHW arrays transpose at the boundary. ``cfg`` is the ViTConfig."""
    sd = state_dict
    tcfg = cfg.transformer

    def lin(key):
        return _lin(sd, key)

    def block(i):
        p = f"vit.encoder.layer.{i}."
        return _hf_encoder_block(sd, p, p + "attention.attention.",
                                 ln1="layernorm_before",
                                 ln2="layernorm_after")

    emb = "vit.embeddings."
    pos = _np(sd[emb + "position_embeddings"])[0]     # [N+1, E]
    if pos.shape[0] != cfg.num_patches + 1:
        # no slicing here (unlike text wpe): the patch grid must match —
        # a resolution/patch-size mismatch needs interpolation, not a crop
        raise ValueError(
            f"checkpoint has {pos.shape[0]} patch positions but the config "
            f"({cfg.image_size}px / {cfg.patch_size}px patches) needs "
            f"{cfg.num_patches + 1}")
    return _finish({"params": {
        "embed": {
            "patch_embed": {
                "kernel": _convw(
                    sd[emb + "patch_embeddings.projection.weight"]),
                "bias": _np(sd[emb + "patch_embeddings.projection.bias"])},
            "cls": _np(sd[emb + "cls_token"]),            # [1, 1, E]
            "pos_embed": pos,
        },
        "encoder": _stack_blocks(
            [block(i) for i in range(tcfg.num_layers)], tcfg.scan_layers),
        "ln_f": {"scale": _np(sd["vit.layernorm.weight"]),
                 "bias": _np(sd["vit.layernorm.bias"])},
        "head": {"kernel": lin("classifier.weight"),
                 "bias": _np(sd["classifier.bias"])},
    }}, tcfg)


def _convw(t) -> np.ndarray:
    """torch Conv2d kernel [O, I, kh, kw] → flax NHWC kernel [kh, kw, I, O]."""
    return _np(t).transpose(2, 3, 1, 0)


def _bn_pair(sd, p: str) -> tuple[dict, dict]:
    """One torch BatchNorm's tensors → (our params {scale, bias},
    our batch_stats {mean, var}). ``num_batches_tracked`` is dropped: it
    only feeds torch's momentum=None cumulative-average mode; our EMA is
    momentum-based (training/trainer.py BN_EMA_MOMENTUM)."""
    return ({"scale": _np(sd[p + "weight"]), "bias": _np(sd[p + "bias"])},
            {"mean": _np(sd[p + "running_mean"]),
             "var": _np(sd[p + "running_var"])})


def resnet_params_from_torch(state_dict, cfg) -> dict:
    """torchvision ResNet ``state_dict()`` → ``{"params": ...,
    "batch_stats": ...}`` for models/resnet.ResNet — the migration bridge
    for the reference's own vision model (``ModelParallelResNet50`` is
    built from torchvision's resnet50, reference
    03_model_parallel.ipynb:325-349 (cell 5); BASELINE config[1]).

    Handles both block types (Bottleneck: resnet50-style conv1..3;
    BasicBlock: resnet18-style conv1..2) and the downsample branch
    (torch ``downsample.0/.1`` → our ``down_conv``/``down_bn``). Conv
    kernels relayout NCHW→NHWC; BN ``weight/bias`` become scale/bias
    params and ``running_mean/var`` become the "batch_stats" EMA buffers
    torch semantics call non-parameter state — exactly how our Trainer
    carries them (buffers outside the optimizer tree).

    Requires ``cfg.torch_padding=True``: under XLA SAME the stride-2
    convs and the stem max-pool pad asymmetrically, so torch weights in a
    SAME model would see every spatial activation shifted — close-enough
    logits that silently aren't the released model. Build with
    ``resnet50(torch_padding=True)``."""
    sd = state_dict
    if not cfg.torch_padding:
        raise ValueError(
            "torch weights need torch conv padding: build the model with "
            "resnet50(torch_padding=True) — XLA SAME pads stride-2 convs "
            "asymmetrically and would shift every activation")
    n_classes = _np(sd["fc.weight"]).shape[0]
    if n_classes != cfg.num_classes:
        raise ValueError(f"checkpoint fc has {n_classes} classes, config "
                         f"has {cfg.num_classes}")
    convs = ("conv1", "conv2", "conv3") if cfg.bottleneck else (
        "conv1", "conv2")

    params: dict = {}
    stats: dict = {}
    params["stem_conv"] = {"kernel": _convw(sd["conv1.weight"])}
    params["stem_bn"], stats["stem_bn"] = _bn_pair(sd, "bn1.")
    for stage, n_blocks in enumerate(cfg.stage_sizes):
        for b in range(n_blocks):
            t = f"layer{stage + 1}.{b}."          # torchvision naming
            ours = f"stage{stage + 1}_block{b}"   # models/resnet naming
            bp: dict = {}
            bs: dict = {}
            for i, conv in enumerate(convs, start=1):
                bp[conv] = {"kernel": _convw(sd[t + f"conv{i}.weight"])}
                bp[f"bn{i}"], bs[f"bn{i}"] = _bn_pair(sd, t + f"bn{i}.")
            if t + "downsample.0.weight" in sd:
                bp["down_conv"] = {
                    "kernel": _convw(sd[t + "downsample.0.weight"])}
                bp["down_bn"], bs["down_bn"] = _bn_pair(
                    sd, t + "downsample.1.")
            params[ours] = bp
            stats[ours] = bs
    params["fc"] = {"kernel": _lin(sd, "fc.weight"),
                    "bias": _np(sd["fc.bias"])}
    # all-fp32 on purpose (no _finish): ResNet params/stats are fp32 with
    # bf16 compute via cfg.dtype, matching a model-initialized tree
    return {"params": params, "batch_stats": stats}


def resnet50_params_from_torch(state_dict, cfg) -> dict:
    """`resnet_params_from_torch` under the name the runbooks use."""
    return resnet_params_from_torch(state_dict, cfg)


def llama_params_from_torch(state_dict, cfg, *, rms_norm_eps=None) -> dict:
    """HF ``LlamaForCausalLM.state_dict()`` → ``{"params": ...}`` for
    models/llama.Llama built with ``llama_config(...)``.

    ``rms_norm_eps``: the source checkpoint's ``LlamaConfig.rms_norm_eps``.
    Pass it whenever the HF config is at hand — epsilon lives in the config,
    not the state_dict, so a mismatch cannot be detected from weights alone:
    our preset pins ``norm_eps=1e-5`` (Llama-2/3), but Llama-1 checkpoints
    and HF's ``LlamaConfig`` default use 1e-6, and importing one of those
    under the preset would silently run every RMSNorm with the wrong
    epsilon. A mismatch with ``cfg.norm_eps`` raises; fix it with
    ``llama_config(..., norm_eps=<checkpoint eps>)``."""
    if rms_norm_eps is not None and rms_norm_eps != cfg.norm_eps:
        raise ValueError(
            f"checkpoint rms_norm_eps={rms_norm_eps} != cfg.norm_eps="
            f"{cfg.norm_eps}; build the config with "
            f"llama_config(..., norm_eps={rms_norm_eps})")
    if cfg.tie_embeddings:
        raise ValueError(
            "Llama import expects tie_embeddings=False (the released "
            "models carry a separate lm_head; a tied config would "
            "silently drop it)")
    sd = {k.removeprefix("model."): v for k, v in state_dict.items()}

    def lin(key):
        return _lin(sd, key)

    def block(i):
        p = f"layers.{i}."
        q = lin(p + "self_attn.q_proj.weight")   # [E, H·D]
        k = lin(p + "self_attn.k_proj.weight")   # [E, KV·D]
        v = lin(p + "self_attn.v_proj.weight")
        if cfg.kv_heads == cfg.num_heads:
            # MHA sizes (7b/13b): SelfAttention uses the single fused
            # [E, 3, H·D] qkv stack, not the GQA q+kv split
            attn = {"qkv_kernel": np.stack([q, k, v], axis=1)}
        else:
            attn = {"q_kernel": q, "kv_kernel": np.stack([k, v], axis=1)}
        attn["out"] = {"kernel": lin(p + "self_attn.o_proj.weight")}
        gate = lin(p + "mlp.gate_proj.weight")   # [E, F]
        up = lin(p + "mlp.up_proj.weight")
        return {
            "ln1": {"scale": _np(sd[p + "input_layernorm.weight"])},
            "ln2": {"scale":
                    _np(sd[p + "post_attention_layernorm.weight"])},
            "attn": attn,
            "mlp": {
                "wi_kernel": np.stack([gate, up], axis=1),  # 0=gate 1=up
                "wo": {"kernel": lin(p + "mlp.down_proj.weight")},
            },
        }

    return _finish({"params": {
        "embed": {"tok": {"embedding": _np(sd["embed_tokens.weight"])}},
        "h": _stack_blocks([block(i) for i in range(cfg.num_layers)],
                           cfg.scan_layers),
        "ln_f": {"scale": _np(sd["norm.weight"])},
        "lm_head": {"kernel": _np(state_dict["lm_head.weight"]).T},
    }}, cfg)
