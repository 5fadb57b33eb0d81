"""The `cohere2_moe` family: everything of the benchmark that knows
Command A+ (CohereLabs/command-a-plus-05-2026).

`cfg` is `benchmark/configs/command-a-plus-05-2026.json` as a dict: the
published `config.json` keys (`layer_types`, `sliding_window`, 128 query
heads over 8 key/value heads of 128, `num_experts` of width
`intermediate_size` of which `num_experts_per_tok` are chosen by
`expert_selection_fn` "sigmoid", `num_shared_experts` combined by
`shared_expert_combination_strategy` "average", `use_parallel_block`,
`position_embedding_type` "rope_gptj", `tie_word_embeddings`), with the
chip's share beside them (`experts_held`, `published_num_experts`) and
the deployment's own (`served_positions`, the dtypes).

The reference half is the model as its config and its description give
it, in straightforward `jax.numpy`: a Python loop over the layers, blocks
of query rows, one expert after another, no cache, no paging, no kernel.
Sizes: width `d`, `H` query heads and `K` key/value heads of `d_h`, `E`
experts (`P` published, the router's width) of width `f` of which `k` are
chosen, `S` shared experts of width `f`, no bias anywhere, the head tied
to the embedding. For layer `l` with input `x`:

- `u = (x - mean(x)) / sqrt(var(x) + eps) * g`: LayerNorm with a gain and
  no bias, in float32 (`layer_norm_eps`).
- `q = W_q u` (`H` heads), `k = W_k u`, `v = W_v u` (`K` heads); no bias,
  no query/key norm. Where `layer_types[l]` is "sliding_attention", RoPE
  GPT-J style on `q` and `k` over the whole head (`rotary_pct` 1): dims
  `2i` and `2i + 1` rotated together by `pos * theta^(-2i / d_h)` (theta
  `rope_theta`, no scaling), and position `t` attends `s` with `0 <= t - s
  <= W - 1` (`W = sliding_window`: the window counts the query itself);
  where "full_attention", no positional encoding (NoPE) and every `s <=
  t`. Scores scaled by `d_h^-1/2`; query head `h` reads key head `h //
  (H/K)`. `A = W_o attn`.
- `r = W_r u`, `P` logits in float32; `p = sigmoid(r)`; the `k` largest
  `p` are chosen, with no selection bias; `w = p_chosen / sum(p_chosen)`
  (`norm_topk_prob`). `routed = sum over e in chosen and held of w_e
  W_down,e (silu(W_gate,e u) * W_up,e u)`.
- `shared = (1/S) sum_j W_down,j (silu(W_gate,j u) * W_up,j u)`: the
  shared experts' average.
- Out: `x + A + routed + shared`: the parallel block, one norm, attention
  and the experts side by side (`use_parallel_block`).
- Head: `logits = LN_f(x) E^T`, tied (`tie_word_embeddings`),
  `logit_scale` 1.

Departures from the published model, each also a line of the
configuration's `assumed` or `not_built`:

- the chip's share: only experts `experts_held = [lo, hi)` of the `P` are
  computed and the vocabulary is its slice of rows; what the other chips'
  experts would add is left out (`deployment`);
- the vision tower is not built, nor the dense prefix layers
  (`first_k_dense_replace` 0: there are none);
- the router and the norms' gains are float32, the matrices bfloat16; the
  logits are bf16 products summed and left in float32;
- memory: `forward` computes a layer's keys and values over the whole
  sequence and the rest in blocks of `BLOCK_ROWS` positions: queries in
  blocks of `BLOCK_QUERY_HEADS // H` rows, each block's projected inside
  it (float32 queries of 33,792 positions and 128 heads are 2.2 GB), and
  the experts, routed and shared, one after another (each routed one on
  the block's tokens routed to it, gathered up to `expert_bound`, or on
  every token of the block under a mask where more are), each expert's
  matrices read from the stacked leaf inside the loop (a layer's slice
  taken outside would be a copy); matrices are upcast a leaf at a time.
  `rows` asks for the logits of those positions alone.

The program holds the shared experts as one SwiGLU of width `S f` whose
down projection the loader (`to_program_tree`) divides by `S`: the
average, exact in bfloat16 for a power of two. Its RoPE is split halves
(dim `i` with `i + d_h/2`): the loader permutes each head's query and key
columns so that dims `2i, 2i + 1` land at `i, i + d_h/2`, which turns the
one rotation into the other exactly and leaves every score as it was.

It imports nothing of the program; only `program_model` does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import HIGHEST, act, mm

ACT_BYTES = 2          # bf16: matrices, activations and caches as served
#: positions of one block of a layer's per-position work in the
#: reference (a sixteenth of the served 33,792)
BLOCK_ROWS = 2112
#: rows of the embedding one block of the tied head reads
BLOCK_VOCAB = 4096
#: query rows times query heads of one block of the reference's
#: attention: 16 rows at 128 heads (float32 scores of 16 x 128 heads over
#: 33,792 keys: 0.28 GB a copy)
BLOCK_QUERY_HEADS = 2048
STACKED = ("ln_g", "q_w", "kv_w", "o_w", "router", "e_gate", "e_up",
           "e_down", "s_gate", "s_up", "s_down")
#: the faults the tests plant in the reference's forward, each a reading
#: of the model that the config or its description rules out
FAULTS = ("sequential_block", "shared_summed", "rope_in_full_layer",
          "rope_split_halves")
_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


# -- sizes ----------------------------------------------------------------

def _dims(cfg: dict) -> tuple[int, int, int, int]:
    """(width, query heads, key/value heads, head size)."""
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def _router_width(cfg: dict) -> int:
    return int(cfg.get("published_num_experts", cfg["num_experts"]))


def _held(cfg: dict) -> tuple[int, int]:
    lo, hi = cfg.get("experts_held", [0, cfg["num_experts"]])
    return int(lo), int(hi)


def _experts(cfg: dict) -> tuple[int, int, int, int]:
    """(experts held, chosen a token, expert width, shared experts)."""
    lo, hi = _held(cfg)
    if hi - lo != cfg["num_experts"]:
        raise ValueError("num_experts is the held experts' count: "
                         "experts_held must span it")
    return (hi - lo, cfg["num_experts_per_tok"], cfg["intermediate_size"],
            cfg["num_shared_experts"])


def layer_kinds(cfg: dict) -> list[tuple[bool, int]]:
    """(rope, window) a layer; window 0 attends every position."""
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError(f"layer_types has one entry a layer "
                         f"({cfg['num_hidden_layers']})")
    win = int(cfg["sliding_window"])
    return [(True, win) if t == "sliding_attention" else (False, 0)
            for t in types]


def period(cfg: dict) -> tuple:
    """The shortest pattern of kinds that the layers repeat."""
    kinds = layer_kinds(cfg)
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return tuple(kinds[:n])


def shapes(cfg: dict) -> dict:
    d, h, hk, dh = _dims(cfg)
    e, _, f, sh = _experts(cfg)
    l, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    return {
        "wte": (v, d), "lnf_g": (d,), "ln_g": (l, d),
        "q_w": (l, d, h * dh),
        "kv_w": (l, d, 2 * hk * dh),     # columns [k | v]
        "o_w": (l, h * dh, d),
        "router": (l, d, _router_width(cfg)),
        "e_gate": (l, e, d, f), "e_up": (l, e, d, f),
        "e_down": (l, e, f, d),
        "s_gate": (l, sh, d, f), "s_up": (l, sh, d, f),
        "s_down": (l, sh, f, d),
    }


def positions(cfg: dict) -> int:
    """The longest sequence served: what the serve reference pads to and
    the program's `max_seq_len`."""
    return int(cfg.get("served_positions", cfg["max_position_embeddings"]))


def make_weights(cfg: dict, seed) -> dict:
    """Weights from the seed, jittable (`seed` a uint32): matrices N(0,
    `initializer_range`) rounded to the configuration's `param_dtype`,
    the norms' gains 1 + N(0, `initializer_range`) and the router N(0,
    `router_init_std`, by default the matrices') in float32. No selection
    bias: the published router has none. The program is handed these
    leaves, so both sides hold the same rounded numbers."""
    std = float(cfg.get("initializer_range", 0.02))
    mat = _DTYPES[cfg.get("param_dtype", "bfloat16")]
    key = jax.random.key(seed)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        sd = float(cfg.get("router_init_std", std)) if name == "router" \
            else std
        w = sd * jax.random.normal(jax.random.fold_in(key, i), shape,
                                   jnp.float32)
        if name.endswith("_g"):
            out[name] = 1.0 + w
        else:
            out[name] = w if name == "router" else w.astype(mat)
    return out


def compared_leaves(tree: dict) -> tuple[dict, set]:
    """Every leaf, those of `STACKED` one norm a layer (a training
    comparison would read them; no cell of this family trains)."""
    return dict(tree), set(STACKED)


# -- the reference: forward -------------------------------------------------

def _layer_norm(x, g, eps):
    x = x.astype(jnp.float32)
    c = x - x.mean(-1, keepdims=True)
    return c * jax.lax.rsqrt((c * c).mean(-1, keepdims=True) + eps) * g


def _angles(pos, d: int, theta: float):
    """cos and sin of pair i's angle `pos * theta^(-2i / d)`, [s, 1,
    d/2] for the positions `pos` [s]."""
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    return jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]


def _rope_gptj(x, pos, theta: float):
    """Rotate the last axis of `x` [s, heads, d] by the angles of the
    positions `pos` [s], GPT-J style: dims 2i and 2i + 1 together."""
    cos, sin = _angles(pos, x.shape[-1], theta)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape).astype(x.dtype)


def _rope_halves(x, pos, theta: float):
    """The split-halves rotation (dim i with i + d/2) on the published
    layout: the planted fault `rope_split_halves`."""
    d = x.shape[-1]
    cos, sin = _angles(pos, d, theta)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _up(w, mode: str):
    """A leaf as `mm` wants it: float32 for the reference (upcast here,
    one leaf at a time), as stored otherwise (`mm` rounds it itself)."""
    return w.astype(jnp.float32) if mode == "f32" else w


def _divisor(s: int, most: int) -> int:
    """The largest divisor of `s` that is at most `most`."""
    most = max(1, min(s, most))
    return max(r for r in range(1, most + 1) if s % r == 0)


def _keys_values(cfg, mode, lp, u, rope: bool, window: int, rotate):
    """A layer's keys (rotated where the layer has RoPE) and values of
    every position of `u` [s, width], [s, K, d_h] each; padded in front
    by the window where the layer has one, so that every block of
    queries slices a span of one length."""
    a = act(mode)
    s = u.shape[0]
    _, _, hk, dh = _dims(cfg)
    kv = mm(u, _up(lp["kv_w"], mode), mode).astype(a).reshape(
        s, 2, hk, dh)
    k, v = kv[:, 0], kv[:, 1]
    if rope:
        k = rotate(k, jnp.arange(s), float(cfg["rope_theta"]))
    if window:
        k, v = (jnp.pad(t, [(window - 1, 0), (0, 0), (0, 0)])
                for t in (k, v))
    return k, v


def _attention(cfg, mode, lp, ub, t0, k, v, rope: bool, window: int,
               rotate):
    """Attention of the queries of `ub` [rows, width] (normed, at
    positions `t0 + arange(rows)`) over the layer's keys and values
    (`_keys_values`), in blocks of query rows, each block's queries
    projected inside it."""
    a = act(mode)
    rows_all, d = ub.shape
    s = k.shape[0] - (window - 1 if window else 0)
    _, h, hk, dh = _dims(cfg)
    theta, scale = float(cfg["rope_theta"]), dh ** -0.5
    back = window - 1 if window else 0
    rows = _divisor(rows_all, BLOCK_QUERY_HEADS // h)
    q_w, o_w = _up(lp["q_w"], mode), _up(lp["o_w"], mode)
    pos = jnp.arange(s)

    def block(r0):
        tpos = t0 + r0 + jnp.arange(rows)
        qb = mm(jax.lax.dynamic_slice_in_dim(ub, r0, rows, 0), q_w,
                mode).astype(a).reshape(rows, h, dh)
        if rope:
            qb = rotate(qb, tpos, theta)
        qb = qb.reshape(rows, hk, h // hk, dh)
        if window:
            span = rows + back
            kb, vb = (jax.lax.dynamic_slice_in_dim(t, t0 + r0, span, 0)
                      for t in (k, v))
            spos = t0 + r0 - back + jnp.arange(span)
        else:
            kb, vb, spos = k, v, pos
        dist = tpos[:, None] - spos[None, :]
        live = (spos[None, :] >= 0) & (dist >= 0)
        if window:
            live &= dist <= back
        scores = jnp.einsum("tkgd,skd->kgts", qb, kb, precision=HIGHEST,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(live[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1).astype(a)
        ctx = jnp.einsum("kgts,skd->tkgd", p, vb, precision=HIGHEST,
                         preferred_element_type=jnp.float32)
        return mm(ctx.astype(a).reshape(rows, h * dh), o_w, mode).astype(a)

    return jax.lax.map(block, jnp.arange(0, rows_all, rows)).reshape(
        rows_all, d)


def expert_bound(s: int) -> int:
    """How many tokens one expert's gather holds: all of a short
    sequence, a quarter of a long one (four times the mean load of `s *
    8 / 128`). An expert that draws more is computed on every token
    under a mask instead: the padded tail of a compared request is one
    token repeated thousands of times, and all of it is routed alike."""
    return s if s <= 1024 else s // 4


def _swiglu(x, gate, up, down, mode):
    a = act(mode)
    hdn = (jax.nn.silu(mm(x, _up(gate, mode), mode).astype(jnp.float32))
           * mm(x, _up(up, mode), mode).astype(jnp.float32)).astype(a)
    return mm(hdn, _up(down, mode), mode).astype(jnp.float32)


def _one_of(p, name: str, layer: int, e):
    """Matrix `e` (traced) of layer `layer` of the stacked leaf `name`,
    read from the whole stack inside a loop's body: a layer's slice of
    the stack taken outside the loop would be a copy (512 MB of routed
    experts a leaf at the published widths)."""
    t = p[name]
    return jax.lax.dynamic_slice(
        t, (layer, e) + (0,) * (t.ndim - 2), (1, 1) + t.shape[2:])[0, 0]


def _experts_out(cfg, mode, p, layer: int, u, fault=None):
    """The held experts' part and the shared experts' average for `u`
    [s, width], in float32, the experts of layer `layer` of the stacked
    leaves `p` one after another."""
    s, d = u.shape
    lo, hi = _held(cfg)
    _, k, _, n_shared = _experts(cfg)
    if not (cfg["expert_selection_fn"] == "sigmoid"
            and cfg["norm_topk_prob"]
            and cfg["shared_expert_combination_strategy"] == "average"):
        raise ValueError("the reference is written for sigmoid selection, "
                         "weights normalised over the chosen, and shared "
                         "experts averaged")
    score = jax.nn.sigmoid(jnp.matmul(u.astype(jnp.float32),
                                      p["router"][layer], precision=HIGHEST))
    _, chosen = jax.lax.top_k(score, k)
    picked = jnp.take_along_axis(score, chosen, -1)
    weight = picked / picked.sum(-1, keepdims=True)
    t = jnp.arange(s)[:, None]
    w_all = jnp.zeros_like(score).at[t, chosen].set(weight)
    on_all = jnp.zeros(score.shape, bool).at[t, chosen].set(True)
    bound = expert_bound(s)
    upad = jnp.concatenate([u, jnp.zeros((1, d), u.dtype)])

    def one(acc, ew):
        e, wcol, on = ew
        mats = [_one_of(p, name, layer, e)
                for name in ("e_gate", "e_up", "e_down")]

        def gathered(acc):
            """The expert on the tokens routed to it alone."""
            idx = jnp.nonzero(on, size=bound, fill_value=s)[0]
            wpad = jnp.concatenate([wcol, jnp.zeros((1,), wcol.dtype)])
            return acc.at[idx].add(_swiglu(upad[idx], *mats, mode)
                                   * wpad[idx][:, None])

        def masked(acc):
            """The expert on every token, the others' weight nought."""
            return acc.at[:s].add(_swiglu(u, *mats, mode) * wcol[:, None])

        return jax.lax.cond(on.sum() > bound, masked, gathered, acc), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros((s + 1, d), jnp.float32),
        (jnp.arange(hi - lo), w_all[:, lo:hi].T, on_all[:, lo:hi].T))

    def shared_one(acc, j):
        mats = [_one_of(p, name, layer, j)
                for name in ("s_gate", "s_up", "s_down")]
        return acc + _swiglu(u, *mats, mode), None

    shared, _ = jax.lax.scan(shared_one, jnp.zeros((s, d), jnp.float32),
                             jnp.arange(n_shared))
    if fault != "shared_summed":
        shared = shared / n_shared
    return acc[:s] + shared


def _layer(cfg, mode, p, i, x, rope: bool, window: int, fault=None):
    """Layer `i` over one sequence `x` [s, width]: its keys and values
    over the whole sequence, then everything else a block of
    `BLOCK_ROWS` positions at a time (the queries, attention, the
    experts), so that no float32 tensor of every position but the
    stream, its norm and the keys lives."""
    a = act(mode)
    eps = cfg["layer_norm_eps"]
    s, d = x.shape
    lp = {name: p[name][i] for name in ("ln_g", "q_w", "kv_w", "o_w")}
    rotate = _rope_halves if fault == "rope_split_halves" else _rope_gptj
    u = _layer_norm(x, lp["ln_g"], eps).astype(a)
    k, v = _keys_values(cfg, mode, lp, u, rope, window, rotate)
    rows = _divisor(s, BLOCK_ROWS)

    def block(t0):
        xb, ub = (jax.lax.dynamic_slice_in_dim(t, t0, rows, 0)
                  for t in (x, u))
        attn = _attention(cfg, mode, lp, ub, t0, k, v, rope, window,
                          rotate)
        if fault == "sequential_block":
            # the experts after attention, on the normed stream it left
            xb = (xb + attn).astype(a)
            nb = _layer_norm(xb, lp["ln_g"], eps).astype(a)
            return (xb + _experts_out(cfg, mode, p, i, nb).astype(a)
                    ).astype(a)
        return (xb + attn + _experts_out(cfg, mode, p, i, ub, fault)
                .astype(a)).astype(a)

    return jax.lax.map(block, jnp.arange(0, s, rows)).reshape(s, d)


def _forward_one(cfg, p, tokens, mode, rows=None, fault=None):
    a = act(mode)
    x = p["wte"][tokens].astype(a)
    for i, (rope, window) in enumerate(layer_kinds(cfg)):
        if fault == "rope_in_full_layer" and not window:
            rope = True
        x = _layer(cfg, mode, p, i, x, rope, window, fault)
    if rows is not None:
        x = x[rows]
    x = _layer_norm(x, p["lnf_g"], cfg["layer_norm_eps"]).astype(a)
    return _head(x, p["wte"], mode) * float(cfg.get("logit_scale", 1))


def _head(x, wte, mode):
    """The tied head, `x` [r, width] against the embedding's rows, a
    block of `BLOCK_VOCAB` rows at a time (the table upcast whole is
    0.54 GB at the published width)."""
    v = wte.shape[0]
    rows = _divisor(v, BLOCK_VOCAB)

    def block(v0):
        w = jax.lax.dynamic_slice_in_dim(wte, v0, rows, 0)
        return mm(x, _up(w, mode).T, mode).astype(jnp.float32)

    out = jax.lax.map(block, jnp.arange(0, v, rows))     # [n, r, rows]
    return out.transpose(1, 0, 2).reshape(x.shape[0], v)


def forward(cfg: dict, params: dict, tokens, mode: str = "f32", rows=None,
            fault=None):
    """Logits [b, s, vocab] in float32, one sequence after another; with
    `rows` (positions, [r]) those positions' alone, [b, r, vocab]: the
    head is applied to them only. `fault` plants one of `FAULTS` (the
    tests')."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    return jnp.stack([_forward_one(cfg, params, row, mode, rows, fault)
                      for row in tokens])


# -- the program: its model object and its parameter tree ----------------

def program_model(cfg: dict, mix: dict):
    """The program's model of this configuration: the Llama dialect of
    `models/transformer.py` with a period of layer kinds, LayerNorm with
    no bias, the parallel block, `DroplessMoE` as every block's
    feed-forward with the held share of the experts and the shared
    experts, and the head tied to the embedding. `quant` is "none" in
    every cell; the control switches the program's own int8 path on
    (`--set quant='"int8_fwd"'`)."""
    from pytorchdistributed_tpu.models.llama import Llama, llama_config

    if not (cfg["use_parallel_block"] and cfg["tie_word_embeddings"]
            and cfg["position_embedding_type"] == "rope_gptj"
            and cfg["rotary_pct"] == 1 and cfg["logit_scale"] == 1):
        raise ValueError("the program is built for the parallel block, "
                         "the tied head at logit_scale 1 and GPT-J RoPE "
                         "over the whole head")
    d, h, hk, dh = _dims(cfg)
    _, k, f, n_shared = _experts(cfg)
    opts = {key: mix[key] for key in ("quant",) if key in mix}
    return Llama(llama_config(
        "test", vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], embed_dim=d, num_heads=h,
        num_kv_heads=hk, head_size=dh, mlp_dim=f,
        max_seq_len=positions(cfg), norm="layernorm",
        norm_eps=cfg["layer_norm_eps"], block_form="parallel",
        rope_theta=float(cfg["rope_theta"]), period=period(cfg),
        router_experts=_router_width(cfg), experts_held=_held(cfg),
        experts_per_token=k, moe_dim=f, moe_scoring="sigmoid",
        moe_activation="silu", shared_experts=n_shared,
        tie_embeddings=True, fp32_logits=True,
        dtype=_DTYPES[cfg.get("compute_dtype", "bfloat16")],
        param_dtype=_DTYPES[cfg.get("param_dtype", "bfloat16")], **opts))


def _by_period(cfg: dict):
    per = len(period(cfg))
    return per, cfg["num_hidden_layers"] // per


def rope_permutation(head_dim: int) -> np.ndarray:
    """The columns of one head in the program's order: published dims
    ``0, 2, ..., d - 2`` then ``1, 3, ..., d - 1``, so that the GPT-J
    pair ``(2i, 2i + 1)`` is the split-halves pair ``(i, i + d/2)``."""
    return np.concatenate([np.arange(0, head_dim, 2),
                           np.arange(1, head_dim, 2)])


def _permute_heads(w, heads: int, head_dim: int, order):
    """`w` [..., heads * head_dim] with each head's columns in `order`."""
    lead = w.shape[:-1]
    t = w.reshape(lead + (heads, head_dim))
    return t[..., order].reshape(lead + (heads * head_dim,))


def _shared_divisor(n_shared: int) -> int:
    if n_shared & (n_shared - 1):
        raise ValueError(f"the average of {n_shared} shared experts is "
                         f"folded into their down projection exactly "
                         f"only for a power of two")
    return n_shared


def to_program_tree(w: dict, cfg: dict, mix: dict) -> dict:
    """Benchmark layout (stacked by layer) -> `Llama`'s `params` tree, as
    a loader of a published checkpoint would: each head's query and key
    columns permuted from GPT-J's interleaved pairs to the program's
    split halves, the shared experts side by side as one SwiGLU whose
    down projection carries the average (divided by `S`), a zero
    selection bias (the published router has none); every other leaf as
    it is, grouped by module. Layer ``p * period + j`` is ``layer_<j>``
    of the scanned period ``p``."""
    d, h, hk, dh = _dims(cfg)
    _, _, f, n_shared = _experts(cfg)
    per, n = _by_period(cfg)
    order = rope_permutation(dh)
    q_w = _permute_heads(w["q_w"], h, dh, order)
    kv = w["kv_w"].reshape(w["kv_w"].shape[:2] + (2, hk * dh))
    kv = kv.at[:, :, 0].set(_permute_heads(kv[:, :, 0], hk, dh, order))
    l = cfg["num_hidden_layers"]
    side = {
        "s_gate": w["s_gate"].transpose(0, 2, 1, 3).reshape(
            l, d, n_shared * f),
        "s_up": w["s_up"].transpose(0, 2, 1, 3).reshape(l, d, n_shared * f),
        "s_down": (w["s_down"] / _shared_divisor(n_shared)).astype(
            w["s_down"].dtype).reshape(l, n_shared * f, d)}
    bias = jnp.zeros((l, _router_width(cfg)), jnp.float32)

    def of(t, j):
        return t.reshape((n, per) + t.shape[1:])[:, j]

    block = {f"layer_{j}": {
        "attn": {"q_kernel": of(q_w, j), "kv_kernel": of(kv, j),
                 "out": {"kernel": of(w["o_w"], j)}},
        "ln1": {"scale": of(w["ln_g"], j)},
        "moe": {"router": of(w["router"], j), "router_bias": of(bias, j),
                "e_gate": of(w["e_gate"], j), "e_up": of(w["e_up"], j),
                "e_down": of(w["e_down"], j),
                **{name: of(t, j) for name, t in side.items()}},
    } for j in range(per)}
    return {"params": {
        "embed": {"tok": {"embedding": w["wte"]}}, "h": {"block": block},
        "ln_f": {"scale": w["lnf_g"]}}}


def from_program_tree(tree: dict, cfg: dict, mix: dict) -> dict:
    """The inverse (the zero selection bias dropped)."""
    d, h, hk, dh = _dims(cfg)
    _, _, f, n_shared = _experts(cfg)
    per, n = _by_period(cfg)
    p = tree["params"] if "params" in tree else tree
    block = p["h"]["block"]
    back = np.argsort(rope_permutation(dh))

    def of(*path):
        parts = []
        for j in range(per):
            t = block[f"layer_{j}"]
            for key in path:
                t = t[key]
            parts.append(t)
        t = jnp.stack(parts, 1)                  # [periods, period, ...]
        return t.reshape((n * per,) + t.shape[2:])

    l = n * per
    kv = of("attn", "kv_kernel")
    kv = kv.at[:, :, 0].set(_permute_heads(kv[:, :, 0], hk, dh, back))
    s_down = of("moe", "s_down")
    return {
        "wte": p["embed"]["tok"]["embedding"], "lnf_g": p["ln_f"]["scale"],
        "ln_g": of("ln1", "scale"),
        "q_w": _permute_heads(of("attn", "q_kernel"), h, dh, back),
        "kv_w": kv.reshape(kv.shape[:2] + (-1,)),
        "o_w": of("attn", "out", "kernel"),
        "router": of("moe", "router"), "e_gate": of("moe", "e_gate"),
        "e_up": of("moe", "e_up"), "e_down": of("moe", "e_down"),
        "s_gate": of("moe", "s_gate").reshape(l, d, n_shared, f).transpose(
            0, 2, 1, 3),
        "s_up": of("moe", "s_up").reshape(l, d, n_shared, f).transpose(
            0, 2, 1, 3),
        "s_down": (s_down * _shared_divisor(n_shared)).astype(
            s_down.dtype).reshape(l, n_shared, f, d),
    }


# -- the counts: operations and bytes from shapes -----------------------

def attention_params(cfg: dict) -> int:
    """q, k, v and o of one layer."""
    d, h, hk, dh = _dims(cfg)
    return 2 * d * h * dh + 2 * d * hk * dh


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * _router_width(cfg)


def expert_params(cfg: dict) -> int:
    """Gate, up and down of one expert, routed or shared."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def shared_params(cfg: dict) -> int:
    return cfg["num_shared_experts"] * expert_params(cfg)


def nonrouted_layer_params(cfg: dict) -> int:
    """A layer's matrices every chip of the deployment holds: attention,
    the router and the shared experts."""
    return attention_params(cfg) + router_params(cfg) + shared_params(cfg)


def _held_share(cfg: dict) -> float:
    lo, hi = _held(cfg)
    return (hi - lo) / _router_width(cfg)


def active_layer_params(cfg: dict) -> float:
    """The matrices one token passes through in one layer on this chip:
    attention, the router, the shared experts, and of the routed experts
    the `k * held / P` this chip computes for a token."""
    return (nonrouted_layer_params(cfg)
            + cfg["num_experts_per_tok"] * _held_share(cfg)
            * expert_params(cfg))


def total_params(cfg: dict) -> int:
    d, l = cfg["hidden_size"], cfg["num_hidden_layers"]
    layer = (nonrouted_layer_params(cfg) + d
             + cfg["num_experts"] * expert_params(cfg))
    # the tied embedding, and the last norm's gains
    return l * layer + d * cfg["vocab_size"] + d


def attended_rows(cfg: dict, context):
    """(rows of the full layers' pool, rows of the window layers' pool)
    one query attends, summed over the layers, when it attends `context`
    positions, itself included: a full layer `context` rows, a window
    layer `min(context, window)`."""
    ctx = np.asarray(context, np.float64)
    kinds = layer_kinds(cfg)
    n_win = sum(1 for _, w in kinds if w)
    win = float(cfg["sliding_window"])
    return (len(kinds) - n_win) * ctx, n_win * np.minimum(ctx, win)


def _row_flops(cfg: dict) -> float:
    """Scores and values of one attended row of one layer, all query
    heads."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]


def forward_flops_token(cfg: dict, context: int, head: bool) -> float:
    """One token's forward pass on this chip attending `context`
    positions (itself included); `head` adds the vocabulary slice's
    projection."""
    full, win = attended_rows(cfg, context)
    f = (2.0 * cfg["num_hidden_layers"] * active_layer_params(cfg)
         + _row_flops(cfg) * float(full + win))
    if head:
        f += 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return f


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A whole prompt, causal, sampled from at its last position."""
    full, win = attended_rows(cfg, np.arange(1, prompt_len + 1,
                                             dtype=np.float64))
    return (2.0 * cfg["num_hidden_layers"] * active_layer_params(cfg)
            * prompt_len + _row_flops(cfg) * float(np.sum(full + win))
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (3 x forward) per trained token; every
    position has a target. No cell trains this family (16 bytes a
    parameter do not fit a chip within the cut's floors)."""
    fwd = prefill_flops(cfg, seq_len) / seq_len
    return 3.0 * (fwd + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
                  * (1 - 1 / seq_len))


def train_attention_flops_per_seq(cfg: dict, seq_len: int) -> float:
    full, win = attended_rows(cfg, np.arange(1, seq_len + 1,
                                             dtype=np.float64))
    return 3.0 * _row_flops(cfg) * float(np.sum(full + win))


def row_bytes(cfg: dict) -> int:
    """A key and a value of every key/value head, in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * ACT_BYTES


def kv_bytes_per_position(cfg: dict) -> int:
    """Keys and values of one position over all layers, while the window
    layers still hold it."""
    return cfg["num_hidden_layers"] * row_bytes(cfg)


def routed_experts(cfg: dict) -> int:
    """The held experts of all layers: what a tick's `moe_experts_hit`
    can reach."""
    return cfg["num_hidden_layers"] * cfg["num_experts"]


def expert_bytes(cfg: dict) -> int:
    """One expert of one layer: the unit of the engine's
    `moe_experts_hit`, which sums the distinct held experts a call's
    live tokens chose over the layers (and `summary()` over the
    calls)."""
    return expert_params(cfg) * ACT_BYTES


def dense_weight_bytes(cfg: dict) -> int:
    """Every matrix a tick reads whatever is routed: attention, the
    float32 router and the shared experts of every layer, and the tied
    head (the embedding's whole slice, read for the logits)."""
    return (cfg["num_hidden_layers"] * (
        (attention_params(cfg) + shared_params(cfg)) * ACT_BYTES
        + router_params(cfg) * 4)
        + cfg["hidden_size"] * cfg["vocab_size"] * ACT_BYTES)


def decode_weight_bytes(cfg: dict) -> int:
    """The weights of one tick if every held expert of every layer is
    hit."""
    return dense_weight_bytes(cfg) + routed_experts(cfg) * expert_bytes(cfg)


def attended_bytes(cfg: dict, contexts) -> float:
    """The key and value rows the queries of live streams of `contexts`
    (positions attended, itself included) have to read in one tick: a
    full layer `context` rows, a window layer `min(context, window)`."""
    ctx = np.asarray(contexts, np.float64)
    if not ctx.size:
        return 0.0
    full, win = attended_rows(cfg, ctx)
    return float(np.sum(full + win)) * row_bytes(cfg)


def decode_tick_bytes(cfg: dict, contexts, experts_hit: float) -> float:
    """What one tick has to read: the weights outside the routed experts
    once, the held experts that a live token chose (`experts_hit`:
    distinct experts summed over the layers) and the rows its live
    streams attend."""
    return (dense_weight_bytes(cfg) + experts_hit * expert_bytes(cfg)
            + attended_bytes(cfg, contexts))
