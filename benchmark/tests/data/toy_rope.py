"""The `toy_rope` family of the CPU tests: a second family that is not
GPT-2-shaped, added to a temporary copy of the benchmark as a new file
(`tests/helpers.py`) to prove that a configuration brings its own model.

The dialect the program has in `models/llama.py`: RMSNorm, rotary
positions in the split-halves convention (dimension i paired with
i + d/2), grouped-query heads, SwiGLU, no bias anywhere, an untied
vocabulary projection. `cfg` carries the usual published keys of that
dialect: hidden_size, intermediate_size, num_hidden_layers,
num_attention_heads, num_key_value_heads, max_position_embeddings,
rms_norm_eps, rope_theta, vocab_size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import HIGHEST, act, mm

ACT_BYTES = 2
STACKED = ("ln1_g", "q_w", "kv_w", "o_w", "ln2_g", "gu_w", "down_w")


def _dims(cfg: dict):
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return e, h, cfg["num_key_value_heads"], e // h


# -- the reference: weights and forward ---------------------------------

def shapes(cfg: dict) -> dict:
    e, h, kvh, d = _dims(cfg)
    l, v, f = (cfg["num_hidden_layers"], cfg["vocab_size"],
               cfg["intermediate_size"])
    return {
        "wte": (v, e), "head_w": (e, v), "lnf_g": (e,),
        "ln1_g": (l, e), "ln2_g": (l, e),
        "q_w": (l, e, h * d), "kv_w": (l, e, 2 * kvh * d),
        "o_w": (l, h * d, e),
        "gu_w": (l, e, 2 * f), "down_w": (l, f, e),
    }


def positions(cfg: dict) -> int:
    return int(cfg["max_position_embeddings"])


def make_weights(cfg: dict, seed) -> dict:
    std = float(cfg.get("initializer_range", 0.02))
    key = jax.random.key(seed)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        w = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        out[name] = 1.0 + w if name.endswith("_g") else w
    return out


def compared_leaves(tree: dict) -> tuple[dict, set]:
    """Keys and values, and gate and up, count apart."""
    out, stacked = {}, set()
    for k, v in tree.items():
        parts = {"kv_w": ("k_w", "v_w"), "gu_w": ("gate_w", "up_w")}.get(k)
        if parts:
            out.update(zip(parts, jnp.split(v, 2, axis=-1)))
            stacked.update(parts)
        else:
            out[k] = v
            if k in STACKED:
                stacked.add(k)
    return out, stacked


def _rms(x, g, eps, mode):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
    return (y * g).astype(act(mode))


def _rope(x, theta):
    """x [b, s, heads, d], rotated by position in float32."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    ang = np.arange(s, dtype=np.float32)[:, None] * freqs[None, :]
    c, sn = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


def _block(cfg, mode, x, lp):
    b, s, e = x.shape
    _, h, kvh, d = _dims(cfg)
    a, eps = act(mode), cfg["rms_norm_eps"]
    y = _rms(x, lp["ln1_g"], eps, mode)
    q = mm(y, lp["q_w"], mode).astype(a).reshape(b, s, h, d)
    k, v = (t.reshape(b, s, kvh, d) for t in jnp.split(
        mm(y, lp["kv_w"], mode).astype(a), 2, axis=-1))
    q = _rope(q, cfg["rope_theta"]).astype(a)
    k = _rope(k, cfg["rope_theta"]).astype(a)
    k, v = (jnp.repeat(t, h // kvh, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST,
                        preferred_element_type=jnp.float32) / np.sqrt(d)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(a)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST,
                     preferred_element_type=jnp.float32)
    x = (x + mm(ctx.astype(a).reshape(b, s, h * d), lp["o_w"],
                mode)).astype(a)
    y = _rms(x, lp["ln2_g"], eps, mode)
    gate, up = jnp.split(mm(y, lp["gu_w"], mode).astype(a), 2, axis=-1)
    y = (jax.nn.silu(gate) * up).astype(a)
    return (x + mm(y, lp["down_w"], mode)).astype(a)


def forward(cfg: dict, params: dict, tokens, mode: str = "f32"):
    """Logits [b, s, vocab] in float32."""
    x = params["wte"][tokens].astype(act(mode))

    @jax.checkpoint
    def body(x, lp):
        return _block(cfg, mode, x, lp), None

    x, _ = jax.lax.scan(body, x, {k: params[k] for k in STACKED})
    x = _rms(x, params["lnf_g"], cfg["rms_norm_eps"], mode)
    return mm(x, params["head_w"], mode).astype(jnp.float32)


# -- the program: its model object and its parameter tree ---------------

def _scan(mix: dict) -> bool:
    return bool(mix.get("scan_layers", True))


def program_model(cfg: dict, mix: dict):
    from pytorchdistributed_tpu.models.llama import Llama, llama_config

    opts = {k: mix[k] for k in ("attention", "remat", "remat_policy",
                                "scan_layers", "quant") if k in mix}
    e, h, kvh, _ = _dims(cfg)
    return Llama(llama_config(
        "test", vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], embed_dim=e, num_heads=h,
        num_kv_heads=kvh, mlp_dim=cfg["intermediate_size"],
        max_seq_len=positions(cfg), norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"], **opts))


def to_program_tree(w: dict, cfg: dict, mix: dict) -> dict:
    e, _, kvh, d = _dims(cfg)
    l, f = cfg["num_hidden_layers"], cfg["intermediate_size"]
    block = {
        "attn": {"q_kernel": w["q_w"],
                 "kv_kernel": w["kv_w"].reshape(l, e, 2, kvh * d),
                 "out": {"kernel": w["o_w"]}},
        "ln1": {"scale": w["ln1_g"]}, "ln2": {"scale": w["ln2_g"]},
        "mlp": {"wi_kernel": w["gu_w"].reshape(l, e, 2, f),
                "wo": {"kernel": w["down_w"]}},
    }
    if _scan(mix):
        h = {"block": block}
    else:
        h = {f"block_{i}": jax.tree.map(lambda x, i=i: x[i], block)
             for i in range(l)}
    return {"params": {
        "embed": {"tok": {"embedding": w["wte"]}}, "h": h,
        "ln_f": {"scale": w["lnf_g"]},
        "lm_head": {"kernel": w["head_w"]}}}


def from_program_tree(tree: dict, cfg: dict, mix: dict) -> dict:
    e, _, kvh, d = _dims(cfg)
    l, f = cfg["num_hidden_layers"], cfg["intermediate_size"]
    p = tree["params"] if "params" in tree else tree
    if _scan(mix):
        block = p["h"]["block"]
    else:
        block = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[p["h"][f"block_{i}"] for i in range(l)])
    return {
        "wte": p["embed"]["tok"]["embedding"],
        "head_w": p["lm_head"]["kernel"], "lnf_g": p["ln_f"]["scale"],
        "ln1_g": block["ln1"]["scale"], "ln2_g": block["ln2"]["scale"],
        "q_w": block["attn"]["q_kernel"],
        "kv_w": block["attn"]["kv_kernel"].reshape(l, e, 2 * kvh * d),
        "o_w": block["attn"]["out"]["kernel"],
        "gu_w": block["mlp"]["wi_kernel"].reshape(l, e, 2 * f),
        "down_w": block["mlp"]["wo"]["kernel"],
    }


# -- the counts: operations and bytes from shapes -----------------------

def layer_matmul_params(cfg: dict) -> int:
    """q and o (e x e each), k and v (e x kv_heads x d each), gate, up
    and down."""
    e, _, kvh, d = _dims(cfg)
    return 2 * e * e + 2 * e * kvh * d + 3 * e * cfg["intermediate_size"]


def matmul_params(cfg: dict) -> int:
    """The blocks and the untied vocabulary projection; the embedding is
    a gather."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    e, l = cfg["hidden_size"], cfg["num_hidden_layers"]
    return matmul_params(cfg) + e * cfg["vocab_size"] + (2 * l + 1) * e


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    attn = 12 * cfg["num_hidden_layers"] * seq_len * cfg["hidden_size"] / 2
    return 6.0 * matmul_params(cfg) + attn


def train_attention_flops_per_seq(cfg: dict, seq_len: int) -> float:
    return (6.0 * cfg["num_hidden_layers"] * seq_len * seq_len
            * cfg["hidden_size"])


def forward_flops_token(cfg: dict, context: int, head: bool) -> float:
    e, l = cfg["hidden_size"], cfg["num_hidden_layers"]
    f = 2.0 * l * layer_matmul_params(cfg) + 4.0 * l * e * context
    if head:
        f += 2.0 * e * cfg["vocab_size"]
    return f


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    e, l = cfg["hidden_size"], cfg["num_hidden_layers"]
    ctx_sum = prompt_len * (prompt_len + 1) / 2
    return (2.0 * l * layer_matmul_params(cfg) * prompt_len
            + 4.0 * l * e * ctx_sum + 2.0 * e * cfg["vocab_size"])


def kv_bytes_per_position(cfg: dict) -> int:
    """Keys and values of the KV heads alone: grouped-query heads share
    them."""
    _, _, kvh, d = _dims(cfg)
    return 2 * cfg["num_hidden_layers"] * kvh * d * ACT_BYTES


def decode_weight_bytes(cfg: dict) -> int:
    return matmul_params(cfg) * ACT_BYTES
