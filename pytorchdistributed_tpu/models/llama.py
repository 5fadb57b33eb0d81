"""Llama-family causal LM — the working TPU-native replacement for the
reference's failed ``LlamaForCausalLM.from_pretrained("decanlp/llama-7b-hf",
device_map="auto")`` demo (reference 03_model_parallel.ipynb:86-89, cell 1;
it never ran for lack of network). Here the model is defined natively on the
shared TransformerStack with the Llama dialect knobs flipped (RMSNorm,
SwiGLU, RoPE, grouped-query attention, no biases, untied LM head), so every
parallel strategy — DDP/FSDP/TP/PP/SP and ``--strategy auto``, the
device_map analog (parallel/auto.py) — applies unmodified.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from pytorchdistributed_tpu.models.transformer import (
    Embedder,
    LMHead,
    TransformerConfig,
    TransformerStack,
    _layer_norm,
    check_pipeline_decomposition,
    gather_free_ce,
    make_stage_apply,
    stack_to_stages,
    stages_to_stack,
)


class Llama(nn.Module):
    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        self.embed = Embedder(cfg)
        self.h = TransformerStack(cfg)
        self.ln_f = _layer_norm(cfg, None)
        if not cfg.tie_embeddings:
            self.lm_head = LMHead(cfg)

    @property
    def counters(self) -> tuple:
        """Names of the "counters" collection's vector (the engine's
        summary): what the stack counts on the device a tick."""
        return self.cfg.counter_names

    def _backbone(self, tokens, deterministic):
        x = self.embed(tokens)
        if self.cfg.fp32_residual:
            x = x.astype(jnp.float32)
        x = self.h(x, deterministic=deterministic)
        return self.ln_f(x)

    def __call__(self, tokens, *, deterministic: bool = True):
        x = self._backbone(tokens, deterministic)
        if self.cfg.tie_embeddings:
            # the head is the embedding (Jamba's `tie_word_embeddings`)
            return self.embed.attend(x).astype(jnp.float32)
        return self.lm_head(x).astype(jnp.float32)

    def loss_per_position(self, tokens, targets, *,
                          deterministic: bool = True):
        """Fused chunked-CE head (see GPT2.loss_per_position)."""
        from pytorchdistributed_tpu.ops.fused_ce import chunked_softmax_ce
        from pytorchdistributed_tpu.models.transformer import _cfg_dot_general

        cfg = self.cfg
        x = self._backbone(tokens, deterministic)
        return chunked_softmax_ce(
            x.astype(cfg.dtype), self.lm_head.kernel.astype(cfg.dtype),
            targets, chunk=cfg.ce_chunk, transpose_w=False,
            dot_general=_cfg_dot_general(cfg))

    @nn.nowrap
    def pipeline_parts(self):
        """1F1B decomposition (see GPT2.pipeline_parts): pre = token embed,
        stages = layer groups, head = ln_f + untied lm_head + CE. No tied
        embedding, so grads merge without summing contributions."""
        from pytorchdistributed_tpu.parallel.pipeline import PipelineParts

        cfg = self.cfg
        check_pipeline_decomposition(cfg)

        def split(params):
            pp = params["params"]
            stage = stack_to_stages(pp["h"]["block"], cfg)
            head = {"ln_f": pp["ln_f"], "proj": pp["lm_head"]["kernel"]}
            return pp["embed"], stage, head

        def pre_apply(pre, tokens):
            return Embedder(cfg).apply({"params": pre}, tokens)

        def head_loss(head, h, targets):
            x = _layer_norm(cfg, None).apply({"params": head["ln_f"]}, h)
            logits = x.astype(cfg.dtype) @ head["proj"].astype(cfg.dtype)
            return gather_free_ce(logits, targets).mean()

        def merge_grads(pre_g, stage_g, head_g):
            blocks = stages_to_stack(stage_g, cfg)
            return {"params": {
                "embed": pre_g, "h": {"block": blocks},
                "ln_f": head_g["ln_f"],
                "lm_head": {"kernel": head_g["proj"]},
            }}

        return PipelineParts(
            split, pre_apply, make_stage_apply(cfg), head_loss, merge_grads,
            stage_apply_aux=(make_stage_apply(cfg, aux=True)
                             if cfg.moe_experts > 0 else None))


def llama_config(size: str = "7b", **overrides) -> TransformerConfig:
    """Llama-2/3-style sizes. mlp_dim follows the released models (the
    2/3·4·d multiple-of-256 rule baked in as literals)."""
    presets = {
        "test": dict(num_layers=2, embed_dim=64, num_heads=4, num_kv_heads=2,
                     mlp_dim=128, vocab_size=128, max_seq_len=128),
        "1b": dict(num_layers=16, embed_dim=2048, num_heads=32,
                   num_kv_heads=8, mlp_dim=8192),
        "7b": dict(num_layers=32, embed_dim=4096, num_heads=32,
                   num_kv_heads=32, mlp_dim=11008),
        "8b": dict(num_layers=32, embed_dim=4096, num_heads=32,
                   num_kv_heads=8, mlp_dim=14336, rope_theta=500000.0),
        "13b": dict(num_layers=40, embed_dim=5120, num_heads=40,
                    num_kv_heads=40, mlp_dim=13824),
        "70b": dict(num_layers=80, embed_dim=8192, num_heads=64,
                    num_kv_heads=8, mlp_dim=28672),
    }
    kw = dict(vocab_size=32000, max_seq_len=4096, causal=True,
              norm="rmsnorm", activation="swiglu", rope=True,
              num_kv_heads=None, use_bias=False, tie_embeddings=False,
              # Llama-2/3's released rms_norm_eps. Llama-1 and HF's
              # LlamaConfig default use 1e-6 — override norm_eps to match
              # the checkpoint when importing (torch_import validates via
              # its rms_norm_eps kwarg).
              norm_eps=1e-5)
    kw.update(presets[size])
    kw.update(overrides)
    return TransformerConfig(**kw)
