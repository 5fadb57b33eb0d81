"""The `dots3_note` family: everything of the benchmark that knows
dots3-note-prev's text stack.

`cfg` is `benchmark/configs/dots3-note-prev.json` as a dict: the published
`config.json` keys, with the chip's share beside them (`experts_held`,
`published_n_routed_experts`, `served_positions`).

The reference half is the model as its config and description give it, in
straightforward `jax.numpy`, with no cache, no kernel and no batching:

- pre-norm layers (RMSNorm), no biases, untied head;
- `layer_types[i]` says whether layer i is a full or a sliding layer; the
  first `first_k_dense_replace` layers have a dense SwiGLU, the others a
  mixture of experts;
- a full layer is latent attention (the DeepSeek-V2 form): `c_q =
  RMSNorm(W_dq x)`, per head `[q_nope; q_rope] = W_uq c_q`, `[c_kv; k_rope]
  = W_dkv x`, `c_kv = RMSNorm(c_kv)`, per head `[k_nope; v] = W_ukv c_kv`,
  RoPE on `q_rope` and on the one `k_rope` all heads share, scale
  `1/sqrt(nope + rope)`; with the DeepSeek-V3.2 indexer: `q_I = W_iq c_q`,
  `k_I = LayerNorm(W_ik x)`, `w = W_iw x / sqrt(heads_I * dim_I)`, `I[t, s] =
  sum_j w[t, j] ReLU(q_I[t, j] . k_I[s])`, and t attends the `index_topk`
  positions s <= t of largest I (all of them while there are no more);
- a sliding layer is the same latent form with its own sizes (`swa_*`), no
  indexer, and t attends s with 0 <= t - s < `sliding_window_size`;
- `attention_gate_type: headwise`: `sigmoid(W_g xn)` a head, on its output;
- experts: `s = sigmoid(W_r x)` in float32; the `num_experts_per_tok`
  largest of `s + b`; weights `s_e / sum(chosen s)` times
  `routed_scaling_factor`; SwiGLU experts and one shared SwiGLU.

Departures from the published description, each also a line of the
configuration's `assumed`:

- the chip's share: only experts `experts_held = [lo, hi)` are computed
  (the router keeps its published width), and the vocabulary is the
  slice's; what the absent experts would add is left out;
- `apply_mla_qkv_lora_rescale` is read as `c_q *= sqrt(hidden/q_rank)`,
  `c_kv *= sqrt(hidden/kv_rank)` after their norms, in both layer kinds;
- RoPE in the split-halves convention (dim i with dim i + d/2), on the
  first `qk_rope_head_dim` numbers of the indexer's q and k, with the
  full layer's theta; no fp8, no Hadamard rotation in the indexer;
- memory: `forward` walks the queries in blocks of rows, a sliding layer
  slices its window's keys, and an expert is computed on the tokens
  routed to it alone (a gather under a bound that is checked: an expert
  over it is computed on every token under a mask). The large leaves are
  bfloat16, the type the configuration states, and are upcast where they
  are used.

It imports nothing of the program; only `program_model` does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import HIGHEST, act, mm

ACT_BYTES = 2          # bf16: weights, activations and caches as served
STACKED = ()           # no leaf is stacked by layer: the layers differ
BLOCK_ROWS = 128       # query rows of one block of the reference
_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


# -- sizes ----------------------------------------------------------------

def _kinds(cfg: dict) -> list[str]:
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def _attn(cfg: dict, kind: str) -> dict:
    """Heads and ranks of one layer kind."""
    if kind == "full_attention":
        return dict(heads=cfg["num_attention_heads"],
                    q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
                    nope=cfg["qk_nope_head_dim"],
                    rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
                    theta=float(cfg["rope_theta"]))
    return dict(heads=cfg["swa_num_attention_heads"],
                q_rank=cfg["swa_q_lora_rank"],
                kv_rank=cfg["swa_kv_lora_rank"],
                nope=cfg["swa_qk_nope_head_dim"],
                rope=cfg["swa_qk_rope_head_dim"], v=cfg["swa_v_head_dim"],
                theta=float(cfg["swa_rope_theta"]))


def _router_width(cfg: dict) -> int:
    return int(cfg.get("published_n_routed_experts",
                       cfg["n_routed_experts"]))


def _held(cfg: dict) -> tuple[int, int]:
    lo, hi = cfg.get("experts_held", [0, cfg["n_routed_experts"]])
    return int(lo), int(hi)


def _is_moe(cfg: dict, layer: int) -> bool:
    return layer >= cfg["first_k_dense_replace"]


def layer_shapes(cfg: dict, layer: int) -> dict:
    """{leaf: (shape, is a matrix)} of one layer."""
    d = cfg["hidden_size"]
    kind = _kinds(cfg)[layer]
    a = _attn(cfg, kind)
    h = a["heads"]
    out = {
        "attn_norm": (d,), "wq_a": (d, a["q_rank"]),
        "q_norm": (a["q_rank"],),
        "wq_b": (a["q_rank"], h * (a["nope"] + a["rope"])),
        "wkv_a": (d, a["kv_rank"] + a["rope"]),
        "kv_norm": (a["kv_rank"],),
        "wkv_b": (a["kv_rank"], h * (a["nope"] + a["v"])),
        "wo": (h * a["v"], d), "wg": (d, h), "ffn_norm": (d,),
    }
    if kind == "full_attention":
        ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
        out.update(wiq=(a["q_rank"], ih * idim), wik=(d, idim),
                   ik_norm_g=(idim,), ik_norm_b=(idim,), wiw=(d, ih))
    if _is_moe(cfg, layer):
        fe = cfg["moe_intermediate_size"]
        lo, hi = _held(cfg)
        fs = fe * cfg["n_shared_experts"]
        out.update(router=(d, _router_width(cfg)),
                   router_bias=(_router_width(cfg),),
                   e_gate=(hi - lo, d, fe), e_up=(hi - lo, d, fe),
                   e_down=(hi - lo, fe, d),
                   s_gate=(d, fs), s_up=(d, fs), s_down=(fs, d))
    else:
        f = cfg["intermediate_size"]
        out.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    return out


def shapes(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": (v, d), "final_norm": (d,), "head": (d, v)}
    for i in range(cfg["num_hidden_layers"]):
        out.update({f"l{i}.{k}": s
                    for k, s in layer_shapes(cfg, i).items()})
    return out


def positions(cfg: dict) -> int:
    """The longest sequence served: what the serve reference pads to and
    the program's `max_seq_len`. The published
    `max_position_embeddings` is the model's, not this deployment's."""
    return int(cfg.get("served_positions", cfg["max_position_embeddings"]))


def _leaf_dtype(cfg: dict, name: str, shape) -> jnp.dtype:
    """Matrices are in the configuration's `param_dtype` (bfloat16);
    gains, the router and its bias stay float32 (routing is published in
    float32, and the small leaves cost nothing)."""
    base = name.rsplit(".", 1)[-1]
    if len(shape) == 1 or base == "router":
        return jnp.float32
    return _DTYPES[cfg.get("param_dtype", "bfloat16")]


def make_weights(cfg: dict, seed) -> dict:
    """Weights from the seed, jittable (`seed` a uint32): matrices N(0,
    initializer_range) rounded to bfloat16, gains around 1, the
    LayerNorm's bias and the router's selection bias random (the bias
    N(0, 0.1), so that routing is uneven). The program is handed these
    leaves, so both sides hold the same rounded numbers."""
    std = float(cfg.get("initializer_range", 0.02))
    key = jax.random.key(seed)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        base = name.rsplit(".", 1)[-1]
        sd = (float(cfg.get("router_bias_std", 0.1))
              if base == "router_bias"
              else float(cfg.get("router_init_std", std))
              if base == "router" else std)
        w = sd * jax.random.normal(jax.random.fold_in(key, i), shape,
                                   jnp.float32)
        if base.endswith("norm") or base.endswith("norm_g"):
            w = 1.0 + w
        out[name] = w.astype(_leaf_dtype(cfg, name, shape))
    return out


def compared_leaves(tree: dict) -> tuple[dict, set]:
    """Every leaf, none stacked (a training comparison would read one
    norm a leaf; no cell of this family trains)."""
    return dict(tree), set()


# -- the reference: forward -------------------------------------------------

def _w(p: dict, name: str, mode: str):
    """A leaf as `mm` wants it: float32 for the reference (upcast here,
    one leaf at a time), as stored otherwise (`mm` rounds it itself)."""
    w = p[name]
    return w.astype(jnp.float32) if mode == "f32" else w


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _ln(x, g, b, eps):
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _rope(x, pos, theta: float):
    """Rotate the last axis of `x` ([s, d] or [s, heads, d]) by the
    angles of positions `pos` [s]; split-halves pairing."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _block_rows(s: int) -> int:
    """The largest divisor of `s` that is at most BLOCK_ROWS."""
    return max(r for r in range(1, min(s, BLOCK_ROWS) + 1) if s % r == 0)


def _swiglu(x, gate, up, down, mode):
    a = act(mode)
    h = (jax.nn.silu(mm(x, gate, mode).astype(jnp.float32))
         * mm(x, up, mode).astype(jnp.float32)).astype(a)
    return mm(h, down, mode)


def _attention(cfg, mode, p, pre, kind, xn, taps=None):
    """One layer's attention over one sequence `xn` [s, hidden] (already
    normed), in blocks of query rows. A full layer leaves the positions
    its indexer chose in `taps` (`forward_choices`)."""
    a = act(mode)
    s, d = xn.shape
    g = _attn(cfg, kind)
    h, nope, rope, dv = g["heads"], g["nope"], g["rope"], g["v"]
    eps = cfg["rms_norm_eps"]
    rescale = bool(cfg.get("apply_mla_qkv_lora_rescale", False))
    pos = jnp.arange(s)

    def w(name):
        return _w(p, pre + name, mode)

    c_q = _rms(mm(xn, w("wq_a"), mode), p[pre + "q_norm"], eps)
    kv = mm(xn, w("wkv_a"), mode).astype(jnp.float32)
    c_kv = _rms(kv[:, :g["kv_rank"]], p[pre + "kv_norm"], eps)
    if rescale:
        c_q = c_q * math.sqrt(d / g["q_rank"])
        c_kv = c_kv * math.sqrt(d / g["kv_rank"])
    c_q, c_kv = c_q.astype(a), c_kv.astype(a)
    k_rope = _rope(kv[:, g["kv_rank"]:].astype(a), pos, g["theta"])
    kvu = mm(c_kv, w("wkv_b"), mode).astype(a).reshape(s, h, nope + dv)
    k_nope, v = kvu[..., :nope], kvu[..., nope:]
    gate = jax.nn.sigmoid(
        mm(xn, w("wg"), mode).astype(jnp.float32))            # [s, h]
    scale = 1.0 / math.sqrt(nope + rope)
    full = kind == "full_attention"
    if full:
        ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
        topk = min(int(cfg["index_topk"]), s)
        k_i = _ln(mm(xn, w("wik"), mode), p[pre + "ik_norm_g"],
                  p[pre + "ik_norm_b"], 1e-6).astype(a)
        k_i = jnp.concatenate(
            [_rope(k_i[:, :rope], pos, g["theta"]), k_i[:, rope:]], -1)
        w_i = (mm(xn, w("wiw"), mode).astype(jnp.float32)
               / math.sqrt(ih * idim))                         # [s, ih]
        keys = (k_nope, k_rope, v, k_i)
    else:
        # a sliding layer's keys, padded in front by the window so that
        # every block of queries slices a span of one length
        back = int(cfg["sliding_window_size"]) - 1
        keys = tuple(jnp.pad(t, [(back, 0)] + [(0, 0)] * (t.ndim - 1))
                     for t in (k_nope, k_rope, v))
    rows = _block_rows(s)
    # upcast once, not once a block of rows
    wq_b, wo = w("wq_b"), w("wo")
    wiq = w("wiq") if full else None
    tap = full and taps is not None

    def block(t0):
        sl = lambda t: jax.lax.dynamic_slice_in_dim(t, t0, rows, 0)
        tpos = t0 + jnp.arange(rows)
        q = mm(sl(c_q), wq_b, mode).astype(a).reshape(
            rows, h, nope + rope)
        q_nope = q[..., :nope]
        q_rope = _rope(q[..., nope:], tpos, g["theta"])
        if full:
            kn, kr, vv, ki = keys
            spos = pos
            q_i = mm(sl(c_q), wiq, mode).astype(a).reshape(
                rows, ih, idim)
            q_i = jnp.concatenate(
                [_rope(q_i[..., :rope], tpos, g["theta"]),
                 q_i[..., rope:]], -1)
            dots = jnp.einsum("tjd,sd->tjs", q_i, ki, precision=HIGHEST,
                              preferred_element_type=jnp.float32)
            index = (jax.nn.relu(dots) * sl(w_i)[:, :, None]).sum(1)
            causal = spos[None, :] <= tpos[:, None]
            index = jnp.where(causal, index, -jnp.inf)
            _, chosen = jax.lax.top_k(index, topk)
            live = jnp.zeros((rows, s), bool).at[
                jnp.arange(rows)[:, None], chosen].set(True) & causal
        else:
            span = rows + back
            kn, kr, vv = (jax.lax.dynamic_slice_in_dim(t, t0, span, 0)
                          for t in keys)
            spos = t0 - back + jnp.arange(span)
            dist = tpos[:, None] - spos[None, :]
            live = (spos[None, :] >= 0) & (dist >= 0) & (dist <= back)
        scores = (jnp.einsum("thd,shd->hts", q_nope, kn, precision=HIGHEST,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("thd,sd->hts", q_rope, kr,
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32))
        scores = jnp.where(live[None], scores * scale, -jnp.inf)
        pr = jax.nn.softmax(scores, axis=-1).astype(a)
        ctx = jnp.einsum("hts,shd->thd", pr, vv, precision=HIGHEST,
                         preferred_element_type=jnp.float32)
        ctx = (ctx * sl(gate)[:, :, None]).astype(a)
        out = mm(ctx.reshape(rows, h * dv), wo, mode).astype(a)
        return (out, chosen) if tap else out

    out = jax.lax.map(block, jnp.arange(0, s, rows))
    if tap:
        out, chosen = out
        taps[pre + "index"] = chosen.reshape(s, topk)
    return out.reshape(s, d)


def expert_bound(s: int) -> int:
    """How many tokens one expert's gather holds: all of a short
    sequence, a quarter of a long one (eight times the mean load of `s *
    top_k / experts` at the published 8 of 256). An expert that draws
    more is computed on every token under a mask instead: the padded tail
    of a compared request is one token repeated thousands of times, and
    all of it is routed alike."""
    return s if s <= 1024 else s // 4


def _moe(cfg, mode, p, pre, xn, taps=None):
    """The held experts' part, plus the shared expert, for `xn` [s, d].
    The experts the router chose are left in `taps` (`forward_choices`)."""
    a = act(mode)
    s, d = xn.shape
    lo, hi = _held(cfg)
    k = cfg["num_experts_per_tok"]
    score = jax.nn.sigmoid(jnp.matmul(
        xn.astype(jnp.float32), p[pre + "router"], precision=HIGHEST))
    _, chosen = jax.lax.top_k(score + p[pre + "router_bias"], k)
    if taps is not None:
        taps[pre + "experts"] = chosen
    picked = jnp.take_along_axis(score, chosen, -1)
    weight = picked / picked.sum(-1, keepdims=True) if cfg[
        "norm_topk_prob"] else picked
    weight = weight * float(cfg["routed_scaling_factor"])
    t = jnp.arange(s)[:, None]
    w_all = jnp.zeros_like(score).at[t, chosen].set(weight)
    on_all = jnp.zeros(score.shape, bool).at[t, chosen].set(True)
    bound = expert_bound(s)
    xpad = jnp.concatenate([xn, jnp.zeros((1, d), xn.dtype)])

    def one(acc, ew):
        *mats, wcol, on = ew
        if mode == "f32":
            mats = [m.astype(jnp.float32) for m in mats]

        def gathered(acc):
            """The expert on the tokens routed to it alone."""
            idx = jnp.nonzero(on, size=bound, fill_value=s)[0]
            y = _swiglu(xpad[idx], *mats, mode).astype(jnp.float32)
            wpad = jnp.concatenate([wcol, jnp.zeros((1,), wcol.dtype)])
            return acc.at[idx].add(y * wpad[idx][:, None])

        def masked(acc):
            """The expert on every token, the others' weight nought."""
            y = _swiglu(xn, *mats, mode).astype(jnp.float32)
            return acc.at[:s].add(y * wcol[:, None])

        return jax.lax.cond(on.sum() > bound, masked, gathered, acc), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros((s + 1, d), jnp.float32),
        (p[pre + "e_gate"], p[pre + "e_up"], p[pre + "e_down"],
         w_all[:, lo:hi].T, on_all[:, lo:hi].T))
    shared = _swiglu(xn, _w(p, pre + "s_gate", mode),
                     _w(p, pre + "s_up", mode),
                     _w(p, pre + "s_down", mode), mode)
    return (acc[:s] + shared.astype(jnp.float32)).astype(a)


def _forward_one(cfg, p, tokens, mode, taps=None):
    a = act(mode)
    eps = cfg["rms_norm_eps"]
    x = p["embed"][tokens].astype(a)
    for i, kind in enumerate(_kinds(cfg)):
        pre = f"l{i}."
        xn = _rms(x, p[pre + "attn_norm"], eps).astype(a)
        x = (x + _attention(cfg, mode, p, pre, kind, xn, taps)).astype(a)
        xn = _rms(x, p[pre + "ffn_norm"], eps).astype(a)
        if _is_moe(cfg, i):
            y = _moe(cfg, mode, p, pre, xn, taps)
        else:
            y = _swiglu(xn, _w(p, pre + "w_gate", mode),
                        _w(p, pre + "w_up", mode),
                        _w(p, pre + "w_down", mode), mode)
        x = (x + y).astype(a)
    x = _rms(x, p["final_norm"], eps).astype(a)
    return mm(x, _w(p, "head", mode), mode).astype(jnp.float32)


def forward(cfg: dict, params: dict, tokens, mode: str = "f32"):
    """Logits [b, s, vocab] in float32, one sequence after another."""
    return jnp.stack([_forward_one(cfg, params, row, mode)
                      for row in tokens])


def forward_choices(cfg: dict, params: dict, tokens, mode: str = "f32"):
    """`forward` of ONE sequence `tokens` [s], and the discrete choices it
    made on the way: ``l<i>.experts`` [s, top_k], the experts the router
    of layer i chose for each position, and ``l<i>.index`` [s,
    index_topk], the positions the indexer of a full layer chose (they
    decide something only for a query past `index_topk`). What
    `tools/witness.py` compares between two precisions."""
    taps = {}
    return _forward_one(cfg, params, tokens, mode, taps), taps


# -- the program: its model object and its parameter tree ----------------

def program_model(cfg: dict, mix: dict):
    """The program's model of this configuration. `quant` is "none" in
    every cell; the control switches the program's own int8 path on
    (`--set quant='"int8_fwd"'`)."""
    from pytorchdistributed_tpu.models.latent import (
        LatentConfig,
        LatentDims,
        LatentLM,
    )

    full, swa = (LatentDims(**_attn(cfg, k))
                 for k in ("full_attention", "sliding_attention"))
    return LatentLM(LatentConfig(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        layer_kinds=tuple("full" if k == "full_attention" else "sliding"
                          for k in _kinds(cfg)),
        full=full, sliding=swa,
        sliding_window=cfg["sliding_window_size"],
        index_heads=cfg["index_n_heads"],
        index_dim=cfg["index_head_dim"], index_topk=cfg["index_topk"],
        lora_rescale=bool(cfg.get("apply_mla_qkv_lora_rescale", False)),
        dense_layers=cfg["first_k_dense_replace"],
        mlp_dim=cfg["intermediate_size"],
        moe_dim=cfg["moe_intermediate_size"],
        router_experts=_router_width(cfg), experts_held=_held(cfg),
        experts_per_token=cfg["num_experts_per_tok"],
        shared_experts=cfg["n_shared_experts"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        norm_eps=cfg["rms_norm_eps"], max_seq_len=positions(cfg),
        dtype=_DTYPES[cfg.get("compute_dtype", "bfloat16")],
        param_dtype=_DTYPES[cfg.get("param_dtype", "bfloat16")],
        quant=mix.get("quant", "none")))


_ATTN_LEAVES = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b",
                "wo", "wg")
_INDEX_LEAVES = ("wiq", "wik", "ik_norm_g", "ik_norm_b", "wiw")
_MOE_LEAVES = ("router", "router_bias", "e_gate", "e_up", "e_down",
               "s_gate", "s_up", "s_down")
_MLP_LEAVES = ("w_gate", "w_up", "w_down")


def to_program_tree(w: dict, cfg: dict, mix: dict) -> dict:
    """Benchmark layout -> `LatentLM`'s `params` tree, as a loader of a
    published checkpoint would: the leaves as they are (bfloat16
    matrices stay bfloat16), only grouped by module."""
    tree = {"embed": {"tok": {"embedding": w["embed"]}},
            "ln_f": {"scale": w["final_norm"]},
            "lm_head": {"kernel": w["head"]}}
    for i, kind in enumerate(_kinds(cfg)):
        pre = f"l{i}."
        attn = {k: w[pre + k] for k in _ATTN_LEAVES}
        if kind == "full_attention":
            attn.update({k: w[pre + k] for k in _INDEX_LEAVES})
        ffn = {k: w[pre + k] for k in
               (_MOE_LEAVES if _is_moe(cfg, i) else _MLP_LEAVES)}
        tree[f"layer_{i}"] = {
            "attn_norm": {"scale": w[pre + "attn_norm"]}, "attn": attn,
            "ffn_norm": {"scale": w[pre + "ffn_norm"]}, "ffn": ffn}
    return {"params": tree}


def from_program_tree(tree: dict, cfg: dict, mix: dict) -> dict:
    """The inverse."""
    p = tree["params"] if "params" in tree else tree
    out = {"embed": p["embed"]["tok"]["embedding"],
           "final_norm": p["ln_f"]["scale"],
           "head": p["lm_head"]["kernel"]}
    for i in range(cfg["num_hidden_layers"]):
        layer = p[f"layer_{i}"]
        out[f"l{i}.attn_norm"] = layer["attn_norm"]["scale"]
        out[f"l{i}.ffn_norm"] = layer["ffn_norm"]["scale"]
        for group in ("attn", "ffn"):
            out.update({f"l{i}.{k}": v for k, v in layer[group].items()})
    return out


# -- the counts: operations and bytes from shapes -----------------------

def _matrix_params(cfg: dict, layer: int, experts: bool) -> int:
    """Parameters of the matrices of one layer; the held experts are
    counted only where `experts`."""
    n = 0
    for name, shape in layer_shapes(cfg, layer).items():
        if len(shape) == 1:
            continue
        if name in ("e_gate", "e_up", "e_down") and not experts:
            continue
        n += int(np.prod(shape))
    return n


def total_params(cfg: dict) -> int:
    return int(sum(int(np.prod(s)) for s in shapes(cfg).values()))


def _held_share(cfg: dict) -> float:
    lo, hi = _held(cfg)
    return (hi - lo) / _router_width(cfg)


def _token_matmul_flops(cfg: dict) -> float:
    """The matrices a token passes through whatever its context: every
    projection, the dense and shared FFNs, the router, and of the routed
    experts the `top_k * held / published` this chip computes for a token
    on average."""
    f = 0.0
    for i in range(cfg["num_hidden_layers"]):
        f += 2.0 * _matrix_params(cfg, i, experts=False)
        if _is_moe(cfg, i):
            f += (2.0 * 3 * cfg["hidden_size"]
                  * cfg["moe_intermediate_size"]
                  * cfg["num_experts_per_tok"] * _held_share(cfg))
    return f


def _attended(cfg: dict, kind: str, context):
    """Positions a query attends in a layer of `kind` at `context` live
    positions (itself included)."""
    cap = (cfg["index_topk"] if kind == "full_attention"
           else cfg["sliding_window_size"])
    return np.minimum(context, cap)


def _context_flops(cfg: dict, context) -> float:
    """One token's attention over `context` live positions, summed over
    the layers: scores and values over the positions attended (per head
    `nope + rope` and `v` numbers a position), and in a full layer the
    indexer's pass over every live position."""
    f = 0.0
    for kind in _kinds(cfg):
        g = _attn(cfg, kind)
        f += (2.0 * g["heads"] * (g["nope"] + g["rope"] + g["v"])
              * _attended(cfg, kind, context))
        if kind == "full_attention":
            f += (2.0 * cfg["index_n_heads"]
                  * (cfg["index_head_dim"] + 1) * context)
    return f


def forward_flops_token(cfg: dict, context: int, head: bool) -> float:
    """One token's forward pass attending `context` positions (itself
    included); `head` adds the vocabulary projection."""
    f = _token_matmul_flops(cfg) + float(_context_flops(cfg, context))
    if head:
        f += 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return f


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A whole prompt, causal, sampled from at its last position."""
    ctx = np.arange(1, prompt_len + 1, dtype=np.float64)
    return (_token_matmul_flops(cfg) * prompt_len
            + float(np.sum(_context_flops(cfg, ctx)))
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (3 x forward) per trained token; every
    position has a target, so the head counts at each. No cell trains
    this family (16 bytes a parameter do not fit)."""
    fwd = prefill_flops(cfg, seq_len) / seq_len
    return 3.0 * (fwd + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
                  * (1 - 1 / seq_len))


def train_attention_flops_per_seq(cfg: dict, seq_len: int) -> float:
    ctx = np.arange(1, seq_len + 1, dtype=np.float64)
    return 3.0 * float(np.sum(_context_flops(cfg, ctx)))


def cache_bytes_per_position(cfg: dict, kind: str) -> int:
    """What one position keeps in one layer of `kind`: the latent and the
    shared RoPE key, and in a full layer the indexer's key beside them."""
    g = _attn(cfg, kind)
    n = g["kv_rank"] + g["rope"]
    if kind == "full_attention":
        n += cfg["index_head_dim"]
    return n * ACT_BYTES


def kv_bytes_per_position(cfg: dict) -> int:
    """Cached bytes of one position over all layers while it is inside
    the window (a sliding layer drops it after `sliding_window_size`)."""
    return sum(cache_bytes_per_position(cfg, k) for k in _kinds(cfg))


def expert_bytes(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * ACT_BYTES


def moe_layers(cfg: dict) -> int:
    return sum(1 for i in range(cfg["num_hidden_layers"])
               if _is_moe(cfg, i))


def dense_weight_bytes(cfg: dict) -> int:
    """Every matrix a tick reads whatever is routed: all but the routed
    experts, the head included; the embedding is a gather of a few rows
    and is not."""
    n = cfg["hidden_size"] * cfg["vocab_size"]
    n += sum(_matrix_params(cfg, i, experts=False)
             for i in range(cfg["num_hidden_layers"]))
    return n * ACT_BYTES


def decode_weight_bytes(cfg: dict) -> int:
    """The weights of one tick if every held expert is hit."""
    lo, hi = _held(cfg)
    return dense_weight_bytes(cfg) + (
        moe_layers(cfg) * (hi - lo) * expert_bytes(cfg))


def decode_tick_bytes(cfg: dict, contexts, experts_hit: float) -> float:
    """What one tick has to read: the weights outside the routed experts
    once, the held experts that a live token chose (`experts_hit`:
    distinct experts summed over the expert layers), and for each live
    stream of `contexts` the indexer's keys of every position, the
    latent rows of the positions attended in each full layer and the
    window's rows in each sliding layer."""
    ctx = np.asarray(contexts, np.float64)
    total = dense_weight_bytes(cfg) + experts_hit * expert_bytes(cfg)
    for kind in _kinds(cfg):
        g = _attn(cfg, kind)
        row = (g["kv_rank"] + g["rope"]) * ACT_BYTES
        total += float(np.sum(_attended(cfg, kind, ctx))) * row
        if kind == "full_attention":
            total += float(ctx.sum()) * cfg["index_head_dim"] * ACT_BYTES
    return total
