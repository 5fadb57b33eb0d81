"""The `gpt2` family: everything of the benchmark that knows GPT-2.

The harness finds this file by a configuration's `model_type`
(`manifest.Cell.family`) and reaches a model only through it: the plain
reference's weights and forward pass, the program's model object and the
adapter between the two parameter layouts, and the operations and bytes
that every utilisation and roofline share divides by a measured time.

`cfg` is a configuration file of `benchmark/configs/` as a dict (the
published `config.json` keys: n_layer, n_embd, n_head, n_inner,
vocab_size, n_positions).

The reference half is GPT-2 as published, in straightforward `jax.numpy`:
pre-LN blocks, learned positions, fused q/k/v projection whose columns
are [q | k | v], heads split contiguously, softmax(q k^T / sqrt(d)) with
a causal mask, tanh GELU, tied output embedding. It imports nothing of
the program; only `program_model` does, when a driver calls it.

The counts are what the algorithm needs, never what an implementation
happens to execute: recomputation, padding, logits of positions nobody
samples and copies of the cache are all absent, so a PR that replaces a
kernel is read against the same numerator.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import HIGHEST, act, mm

ACT_BYTES = 2  # bf16: the compute and cache type the configurations state
STACKED = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
           "ln2_g", "ln2_b", "fc_w", "fc_b", "out_w", "out_b")


# -- the reference: weights and forward ---------------------------------

def shapes(cfg: dict) -> dict:
    e, l, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    f = ffn_dim(cfg)
    return {
        "wte": (v, e), "wpe": (cfg["n_positions"], e),
        "ln1_g": (l, e), "ln1_b": (l, e),
        "qkv_w": (l, e, 3 * e), "qkv_b": (l, 3 * e),
        "proj_w": (l, e, e), "proj_b": (l, e),
        "ln2_g": (l, e), "ln2_b": (l, e),
        "fc_w": (l, e, f), "fc_b": (l, f),
        "out_w": (l, f, e), "out_b": (l, e),
        "lnf_g": (e,), "lnf_b": (e,),
    }


def positions(cfg: dict) -> int:
    """The longest sequence the model takes: what the serve reference
    pads to and the program's `max_seq_len`."""
    return int(cfg["n_positions"])


def make_weights(cfg: dict, seed) -> dict:
    """Float32 weights from the seed: N(0, initializer_range) everywhere,
    gains around 1. Biases and gains are random too, so every leaf has a
    gradient and no two rows of anything are alike. `seed` is a uint32
    (`reference.seed_u32`), so it can be a traced argument: jit this with
    the shardings the weights should land in."""
    std = float(cfg.get("initializer_range", 0.02))
    key = jax.random.key(seed)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        w = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        out[name] = 1.0 + w if name.endswith("_g") else w
    return out


def compared_leaves(tree: dict) -> tuple[dict, set]:
    """The leaves whose norms the training comparison takes, and which of
    them are stacked by layer (one norm a layer). The fused q/k/v
    projection counts as three leaves: a key's bias has no gradient under
    softmax, and inside one fused leaf it would hide."""
    out, stacked = {}, set()
    for k, v in tree.items():
        if k in ("qkv_w", "qkv_b"):
            for part, t in zip("qkv", jnp.split(v, 3, axis=-1)):
                out[f"{part}_{k[4:]}"] = t
                stacked.add(f"{part}_{k[4:]}")
        else:
            out[k] = v
            if k in STACKED:
                stacked.add(k)
    return out, stacked


def _ln(x, g, b, eps, mode):
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * g + b).astype(act(mode))


def _gelu(x):
    return 0.5 * x * (1 + jnp.tanh(
        np.sqrt(2 / np.pi) * (x + 0.044715 * x ** 3)))


def _block(cfg, mode, x, lp):
    b, s, e = x.shape
    h = cfg["n_head"]
    d = e // h
    a = act(mode)
    eps = cfg["layer_norm_epsilon"]
    y = _ln(x, lp["ln1_g"], lp["ln1_b"], eps, mode)
    qkv = (mm(y, lp["qkv_w"], mode) + lp["qkv_b"]).astype(a)
    q, k, v = (t.reshape(b, s, h, d) for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST,
                        preferred_element_type=jnp.float32) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(a)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST,
                     preferred_element_type=jnp.float32)
    ctx = ctx.astype(a).reshape(b, s, e)
    x = (x + mm(ctx, lp["proj_w"], mode) + lp["proj_b"]).astype(a)
    y = _ln(x, lp["ln2_g"], lp["ln2_b"], eps, mode)
    y = _gelu((mm(y, lp["fc_w"], mode) + lp["fc_b"]).astype(a))
    return (x + mm(y, lp["out_w"], mode) + lp["out_b"]).astype(a)


def forward(cfg: dict, params: dict, tokens, mode: str = "f32"):
    """Logits [b, s, vocab] in float32. Layers run under `lax.scan` with
    `jax.checkpoint`, so one layer's activations live at once."""
    s = tokens.shape[1]
    x = (params["wte"][tokens] + params["wpe"][:s]).astype(act(mode))
    stacked = {k: params[k] for k in STACKED}

    @jax.checkpoint
    def body(x, lp):
        return _block(cfg, mode, x, lp), None

    x, _ = jax.lax.scan(body, x, stacked)
    x = _ln(x, params["lnf_g"], params["lnf_b"],
            cfg["layer_norm_epsilon"], mode)
    return mm(x, params["wte"].T, mode).astype(jnp.float32)


# -- the program: its model object and its parameter tree ---------------

def _scan(mix: dict) -> bool:
    # the program's default where a mix (every serve mix) does not say
    return bool(mix.get("scan_layers", True))


def program_model(cfg: dict, mix: dict):
    """The program's model of this configuration, with what the mix
    states of the program's own options. `quant` is "none" in every cell;
    the control switches the program's own int8 path on
    (`--set quant='"int8"'`, serving `'"int8_fwd"'`)."""
    from pytorchdistributed_tpu.models import GPT2
    from pytorchdistributed_tpu.models.transformer import TransformerConfig

    opts = {k: mix[k] for k in ("attention", "remat", "remat_policy",
                                "scan_layers", "quant") if k in mix}
    return GPT2(TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
        embed_dim=cfg["n_embd"], num_heads=cfg["n_head"],
        mlp_dim=cfg.get("n_inner"), max_seq_len=positions(cfg),
        causal=True, norm_eps=cfg["layer_norm_epsilon"], **opts))


def to_program_tree(w: dict, cfg: dict, mix: dict) -> dict:
    """Benchmark layout (stacked by layer) -> `GPT2`'s `params` tree, as
    a loader of a published checkpoint would."""
    e, l = cfg["n_embd"], cfg["n_layer"]
    block = {
        "attn": {"qkv_kernel": w["qkv_w"].reshape(l, e, 3, e),
                 "qkv_bias": w["qkv_b"].reshape(l, 3, e),
                 "out": {"kernel": w["proj_w"], "bias": w["proj_b"]}},
        "ln1": {"scale": w["ln1_g"], "bias": w["ln1_b"]},
        "ln2": {"scale": w["ln2_g"], "bias": w["ln2_b"]},
        "mlp": {"wi": {"kernel": w["fc_w"], "bias": w["fc_b"]},
                "wo": {"kernel": w["out_w"], "bias": w["out_b"]}},
    }
    if _scan(mix):
        h = {"block": block}
    else:
        h = {f"block_{i}": jax.tree.map(lambda x, i=i: x[i], block)
             for i in range(l)}
    return {"params": {
        "embed": {"tok": {"embedding": w["wte"]}, "pos": w["wpe"]},
        "h": h,
        "ln_f": {"scale": w["lnf_g"], "bias": w["lnf_b"]}}}


def from_program_tree(tree: dict, cfg: dict, mix: dict) -> dict:
    """The inverse, for reading gradients and changes back."""
    e, l = cfg["n_embd"], cfg["n_layer"]
    p = tree["params"] if "params" in tree else tree
    if _scan(mix):
        block = p["h"]["block"]
    else:
        block = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[p["h"][f"block_{i}"] for i in range(l)])
    return {
        "wte": p["embed"]["tok"]["embedding"], "wpe": p["embed"]["pos"],
        "ln1_g": block["ln1"]["scale"], "ln1_b": block["ln1"]["bias"],
        "qkv_w": block["attn"]["qkv_kernel"].reshape(l, e, 3 * e),
        "qkv_b": block["attn"]["qkv_bias"].reshape(l, 3 * e),
        "proj_w": block["attn"]["out"]["kernel"],
        "proj_b": block["attn"]["out"]["bias"],
        "ln2_g": block["ln2"]["scale"], "ln2_b": block["ln2"]["bias"],
        "fc_w": block["mlp"]["wi"]["kernel"],
        "fc_b": block["mlp"]["wi"]["bias"],
        "out_w": block["mlp"]["wo"]["kernel"],
        "out_b": block["mlp"]["wo"]["bias"],
        "lnf_g": p["ln_f"]["scale"], "lnf_b": p["ln_f"]["bias"],
    }


# -- the counts: operations and bytes from shapes -----------------------

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
