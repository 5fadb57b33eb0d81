"""Operations and bytes a GPT-2-shaped model needs, worked out from shapes.

Every utilisation and roofline share of this benchmark divides one of
these by a measured time. They count what the algorithm needs, never what
an implementation happens to execute: recomputation, padding, logits of
positions nobody samples and copies of the cache are all absent, so a PR
that replaces a kernel is read against the same numerator.

`cfg` is a configuration file of `benchmark/configs/` as a dict (the
published `config.json` keys: n_layer, n_embd, n_head, n_inner,
vocab_size, n_positions).
"""

from __future__ import annotations

ACT_BYTES = 2  # bf16: the compute and cache type the configurations state


def ffn_dim(cfg: dict) -> int:
    return int(cfg.get("n_inner") or 4 * cfg["n_embd"])


def layer_matmul_params(cfg: dict) -> int:
    """q, k, v, o projections and the two MLP matrices of one block."""
    e = cfg["n_embd"]
    return 4 * e * e + 2 * e * ffn_dim(cfg)


def matmul_params(cfg: dict) -> int:
    """Every weight that is the operand of a matmul: the blocks and the
    (tied) vocabulary projection. Position rows and norms are not."""
    return (cfg["n_layer"] * layer_matmul_params(cfg)
            + cfg["n_embd"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    e, l = cfg["n_embd"], cfg["n_layer"]
    per_layer = layer_matmul_params(cfg) + 4 * e + ffn_dim(cfg) + 4 * e + e
    # biases: qkv 3e + out e, mlp f + e; two norms 4e
    return (l * per_layer + e * cfg["vocab_size"]
            + cfg["n_positions"] * e + 2 * e)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (3 x forward) per trained token: 6 x matmul
    parameters plus causal attention, 12 L S E halved because half the
    score matrix is masked. Recomputed operations are not counted."""
    attn = 12 * cfg["n_layer"] * seq_len * cfg["n_embd"] * 0.5
    return 6.0 * matmul_params(cfg) + attn


def train_attention_flops_per_seq(cfg: dict, seq_len: int) -> float:
    """Causal attention alone, forward and backward, of one sequence:
    QK^T and PV are 2 S^2 E each forward, halved by the mask, times 3."""
    return 6.0 * cfg["n_layer"] * seq_len * seq_len * cfg["n_embd"]


def forward_flops_token(cfg: dict, context: int, head: bool) -> float:
    """One token's forward pass attending `context` positions (itself
    included); `head` adds the vocabulary projection, which only a
    position that is sampled from needs."""
    e, l = cfg["n_embd"], cfg["n_layer"]
    f = 2.0 * l * layer_matmul_params(cfg) + 4.0 * l * e * context
    if head:
        f += 2.0 * e * cfg["vocab_size"]
    return f


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A whole prompt, causal, sampled from at its last position."""
    e, l = cfg["n_embd"], cfg["n_layer"]
    ctx_sum = prompt_len * (prompt_len + 1) / 2
    return (2.0 * l * layer_matmul_params(cfg) * prompt_len
            + 4.0 * l * e * ctx_sum + 2.0 * e * cfg["vocab_size"])


def kv_bytes_per_position(cfg: dict) -> int:
    """Keys and values of one position over all layers, in bf16."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * ACT_BYTES


def decode_weight_bytes(cfg: dict) -> int:
    """What one decode tick has to read of the weights, once, in the
    compute type: every matmul weight (the tied head included)."""
    return matmul_params(cfg) * ACT_BYTES
