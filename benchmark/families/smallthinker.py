"""The `smallthinker` family: everything of the benchmark that knows
SmallThinker (PowerInfer/SmallThinker-21BA3B-Instruct).

`cfg` is `benchmark/configs/smallthinker-21ba3b.json` as a dict: the
published `config.json` keys (`rope_layout`, `sliding_window_layout`,
`sliding_window_size`, 28 query heads over 4 key/value heads of 128,
`moe_num_primary_experts` 64 of width `moe_ffn_hidden_size` of which
`moe_num_active_primary_experts` 6 are chosen,
`moe_primary_router_apply_softmax`, `norm_topk_prob`), with the
deployment's own beside them (`served_positions`, the dtypes).

The reference half is the model as its config and its description give
it, in straightforward `jax.numpy`: a Python loop over the layers, blocks
of query rows, one expert after another, no cache, no paging, no kernel.
Sizes: width `d`, `H` query heads and `K` key/value heads of `d_h`, `E`
experts of width `f` of which `k` are chosen, no bias anywhere, untied
head. For layer `l` with input `x`:

- `u = RMS_1(x)` (`x / sqrt(mean(x^2) + eps) * g`). `r = W_r u`, `E`
  logits in float32: the router reads `u`, the tensor attention reads
  ("router placed before attention").
- `q = W_q u`, `k = W_k u`, `v = W_v u`. Where `rope_layout[l]` is 1, RoPE
  on `q` and `k` over the whole head (theta `rope_theta`, no scaling, dim
  `i` paired with `i + d_h/2`); where 0, nothing (NoPE). Scores scaled by
  `d_h^-1/2`; query head `h` reads key head `h // (H/K)`. Where
  `sliding_window_layout[l]` is 1, position `t` attends `s` with `0 <= t -
  s <= W - 1` (`W = sliding_window_size`: the window counts the query
  itself); where 0, every `s <= t`. `x' = x + W_o attn`.
- `n = RMS_2(x')`. The `k` largest of `r` are chosen; `w = softmax` over
  those `k` logits in float32 (`moe_primary_router_apply_softmax` with
  `norm_topk_prob`: a softmax over all `E`, renormalised over the chosen,
  gives the same numbers). `y = sum_e w_e W_down,e (relu(W_gate,e n) *
  W_up,e n)`: ReGLU experts. Out: `x' + y`. No shared expert, no dense
  layer, no selection bias.
- Head: `W_head RMS_f(x)`.

Departures from the published model, each also a line of the
configuration's `assumed` or `not_built`:

- the config has no key for a bias or for a query/key norm: none is built;
- the secondary experts and the sparsity predictor the family is described
  as having have no keys in the config: not built;
- the router and the norms' gains are float32, the matrices bfloat16; the
  logits are bf16 products summed and left in float32;
- memory: `forward` walks a layer's queries in blocks of `BLOCK_ROWS`
  (float32 scores of all 28 heads over 16,384 keys: 0.47 GB a block) and
  its experts one after another (each on the tokens routed to it, gathered
  up to `expert_bound`, or on every token under a mask where more are);
  matrices are upcast a leaf at a time. `rows` asks for the logits of
  those positions alone: whole float32 logits of 16,384 positions over
  151,936 words are 9.96 GB, more than the chip has beside the weights.

It imports nothing of the program; only `program_model` does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import HIGHEST, act, mm

ACT_BYTES = 2          # bf16: matrices, activations and caches as served
BLOCK_ROWS = 256       # query rows of one block of the reference
STACKED = ("ln1_g", "ln2_g", "q_w", "kv_w", "o_w", "router", "e_gate",
           "e_up", "e_down")
#: the faults the tests plant in the reference's forward, each a reading
#: of the model that the config or its description rules out
FAULTS = ("rope_in_full_layer", "no_rope_in_window_layer",
          "router_after_attention", "window_off_by_one")
_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


# -- sizes ----------------------------------------------------------------

def _dims(cfg: dict) -> tuple[int, int, int, int]:
    """(width, query heads, key/value heads, head size)."""
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def _experts(cfg: dict) -> tuple[int, int, int]:
    """(experts, chosen a token, expert width)."""
    return (cfg["moe_num_primary_experts"],
            cfg["moe_num_active_primary_experts"],
            cfg["moe_ffn_hidden_size"])


def layer_kinds(cfg: dict) -> list[tuple[bool, int]]:
    """(rope, window) a layer; window 0 attends every position."""
    l = cfg["num_hidden_layers"]
    ropes, wins = cfg["rope_layout"], cfg["sliding_window_layout"]
    if len(ropes) != l or len(wins) != l:
        raise ValueError(f"rope_layout and sliding_window_layout have one "
                         f"entry a layer ({l})")
    return [(bool(r), int(cfg["sliding_window_size"]) if w else 0)
            for r, w in zip(ropes, wins)]


def period(cfg: dict) -> tuple:
    """The shortest pattern of kinds that the layers repeat."""
    kinds = layer_kinds(cfg)
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return tuple(kinds[:n])


def shapes(cfg: dict) -> dict:
    d, h, hk, dh = _dims(cfg)
    e, _, f = _experts(cfg)
    l, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    return {
        "wte": (v, d), "head_w": (d, v), "lnf_g": (d,),
        "ln1_g": (l, d), "ln2_g": (l, d),
        "q_w": (l, d, h * dh),
        "kv_w": (l, d, 2 * hk * dh),     # columns [k | v]
        "o_w": (l, h * dh, d),
        "router": (l, d, e),
        "e_gate": (l, e, d, f), "e_up": (l, e, d, f),
        "e_down": (l, e, f, d),
    }


def positions(cfg: dict) -> int:
    """The longest sequence served: what the serve reference pads to and
    the program's `max_seq_len`."""
    return int(cfg.get("served_positions", cfg["max_position_embeddings"]))


def make_weights(cfg: dict, seed) -> dict:
    """Weights from the seed, jittable (`seed` a uint32): matrices N(0,
    `initializer_range`) rounded to the configuration's `param_dtype`,
    the norms' gains 1 + N(0, `initializer_range`) and the router N(0,
    `router_init_std`, by default the matrices') in float32. No bias is
    invented for the router: the experts' balance is what random weights
    give. The program is handed these leaves, so both sides hold the same
    rounded numbers."""
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

def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, pos, theta: float):
    """Rotate the last axis of `x` [s, heads, d] by the angles of the
    positions `pos` [s]; dim i pairs with dim i + d/2."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _up(w, mode: str):
    """A leaf as `mm` wants it: float32 for the reference (upcast here,
    one leaf at a time), as stored otherwise (`mm` rounds it itself)."""
    return w.astype(jnp.float32) if mode == "f32" else w


def _block_rows(s: int) -> int:
    """The largest divisor of `s` that is at most BLOCK_ROWS."""
    return max(r for r in range(1, min(s, BLOCK_ROWS) + 1) if s % r == 0)


def _attention(cfg, mode, lp, u, rope: bool, window: int):
    """One layer's attention over one sequence `u` [s, width] (already
    normed), in blocks of query rows."""
    a = act(mode)
    s, d = u.shape
    _, h, hk, dh = _dims(cfg)
    theta, scale = float(cfg["rope_theta"]), dh ** -0.5
    pos = jnp.arange(s)
    q = mm(u, _up(lp["q_w"], mode), mode).astype(a).reshape(s, h, dh)
    kv = mm(u, _up(lp["kv_w"], mode), mode).astype(a).reshape(
        s, 2, hk, dh)
    k, v = kv[:, 0], kv[:, 1]
    if rope:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    back = window - 1 if window else 0
    if window:
        # padded in front by the window, so that every block of queries
        # slices a span of one length
        k, v = (jnp.pad(t, [(back, 0), (0, 0), (0, 0)]) for t in (k, v))
    rows = _block_rows(s)
    o_w = _up(lp["o_w"], mode)

    def block(t0):
        tpos = t0 + jnp.arange(rows)
        qb = jax.lax.dynamic_slice_in_dim(q, t0, rows, 0).reshape(
            rows, hk, h // hk, dh)
        if window:
            span = rows + back
            kb, vb = (jax.lax.dynamic_slice_in_dim(t, t0, span, 0)
                      for t in (k, v))
            spos = t0 - back + jnp.arange(span)
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

    return jax.lax.map(block, jnp.arange(0, s, rows)).reshape(s, d)


def expert_bound(s: int) -> int:
    """How many tokens one expert's gather holds: all of a short
    sequence, a quarter of a long one (2.7 times the mean load of `s * 6 /
    64`). An expert that draws more is computed on every token under a
    mask instead: the padded tail of a compared request is one token
    repeated thousands of times, and all of it is routed alike."""
    return s if s <= 1024 else s // 4


def _experts_out(cfg, mode, lp, n, r):
    """The experts' part for `n` [s, width] under the router's logits `r`
    [s, experts] (float32)."""
    a = act(mode)
    s, d = n.shape
    e, k, _ = _experts(cfg)
    if not (cfg["moe_primary_router_apply_softmax"]
            and cfg["norm_topk_prob"]):
        raise ValueError("the reference is written for a router whose "
                         "weights are a softmax of its logits, "
                         "normalised over the chosen")
    top, chosen = jax.lax.top_k(r, k)
    weight = jax.nn.softmax(top, -1)
    t = jnp.arange(s)[:, None]
    w_all = jnp.zeros_like(r).at[t, chosen].set(weight)
    on_all = jnp.zeros(r.shape, bool).at[t, chosen].set(True)
    bound = expert_bound(s)
    npad = jnp.concatenate([n, jnp.zeros((1, d), n.dtype)])

    def reglu(x, gate, up, down):
        hdn = (jax.nn.relu(mm(x, gate, mode).astype(jnp.float32))
               * mm(x, up, mode).astype(jnp.float32)).astype(a)
        return mm(hdn, down, mode).astype(jnp.float32)

    def one(acc, ew):
        *mats, wcol, on = ew
        mats = [_up(m, mode) for m in mats]

        def gathered(acc):
            """The expert on the tokens routed to it alone."""
            idx = jnp.nonzero(on, size=bound, fill_value=s)[0]
            wpad = jnp.concatenate([wcol, jnp.zeros((1,), wcol.dtype)])
            return acc.at[idx].add(reglu(npad[idx], *mats)
                                   * wpad[idx][:, None])

        def masked(acc):
            """The expert on every token, the others' weight nought."""
            return acc.at[:s].add(reglu(n, *mats) * wcol[:, None])

        return jax.lax.cond(on.sum() > bound, masked, gathered, acc), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros((s + 1, d), jnp.float32),
        (lp["e_gate"], lp["e_up"], lp["e_down"], w_all.T, on_all.T))
    return acc[:s].astype(a)


def _forward_one(cfg, p, tokens, mode, rows=None, fault=None):
    a = act(mode)
    eps = cfg["rms_norm_eps"]
    x = p["wte"][tokens].astype(a)
    for i, (rope, window) in enumerate(layer_kinds(cfg)):
        lp = {name: p[name][i] for name in STACKED}
        if fault == "rope_in_full_layer" and not window:
            rope = True
        if fault == "no_rope_in_window_layer" and window:
            rope = False
        if fault == "window_off_by_one" and window:
            window += 1
        u = _rms(x, lp["ln1_g"], eps).astype(a)
        x = (x + _attention(cfg, mode, lp, u, rope, window)).astype(a)
        n = _rms(x, lp["ln2_g"], eps).astype(a)
        routed = n if fault == "router_after_attention" else u
        r = jnp.matmul(routed.astype(jnp.float32), lp["router"],
                       precision=HIGHEST)
        x = (x + _experts_out(cfg, mode, lp, n, r)).astype(a)
    if rows is not None:
        x = x[rows]
    x = _rms(x, p["lnf_g"], eps).astype(a)
    return mm(x, _up(p["head_w"], mode), mode).astype(jnp.float32)


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
    `models/transformer.py` with a period of layer kinds and
    `DroplessMoE` as every block's feed-forward, all experts held.
    `quant` is "none" in every cell; the control switches the program's
    own int8 path on (`--set quant='"int8_fwd"'`)."""
    from pytorchdistributed_tpu.models.llama import Llama, llama_config

    d, h, hk, dh = _dims(cfg)
    e, k, f = _experts(cfg)
    opts = {key: mix[key] for key in ("quant",) if key in mix}
    return Llama(llama_config(
        "test", vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], embed_dim=d, num_heads=h,
        num_kv_heads=hk, head_size=dh, mlp_dim=f,
        max_seq_len=positions(cfg), norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), period=period(cfg),
        router_experts=e, experts_held=(0, e), experts_per_token=k,
        moe_dim=f, moe_scoring="softmax", moe_activation="relu",
        router_input="attn", fp32_logits=True,
        dtype=_DTYPES[cfg.get("compute_dtype", "bfloat16")],
        param_dtype=_DTYPES[cfg.get("param_dtype", "bfloat16")], **opts))


def _by_period(cfg: dict):
    per = len(period(cfg))
    return per, cfg["num_hidden_layers"] // per


def to_program_tree(w: dict, cfg: dict, mix: dict) -> dict:
    """Benchmark layout (stacked by layer) -> `Llama`'s `params` tree, as
    a loader of a published checkpoint would: the leaves as they are,
    grouped by module; layer ``p * period + j`` is ``layer_<j>`` of the
    scanned period ``p``."""
    _, _, hk, dh = _dims(cfg)
    per, n = _by_period(cfg)

    def of(name, j, *shape):
        t = w[name]
        t = t.reshape((n, per) + t.shape[1:])[:, j]
        return t.reshape((n,) + shape) if shape else t

    d = cfg["hidden_size"]
    block = {f"layer_{j}": {
        "attn": {"q_kernel": of("q_w", j),
                 "kv_kernel": of("kv_w", j, d, 2, hk * dh),
                 "out": {"kernel": of("o_w", j)}},
        "ln1": {"scale": of("ln1_g", j)}, "ln2": {"scale": of("ln2_g", j)},
        "moe": {"router": of("router", j), "e_gate": of("e_gate", j),
                "e_up": of("e_up", j), "e_down": of("e_down", j)},
    } for j in range(per)}
    return {"params": {
        "embed": {"tok": {"embedding": w["wte"]}}, "h": {"block": block},
        "ln_f": {"scale": w["lnf_g"]},
        "lm_head": {"kernel": w["head_w"]}}}


def from_program_tree(tree: dict, cfg: dict, mix: dict) -> dict:
    """The inverse."""
    per, n = _by_period(cfg)
    p = tree["params"] if "params" in tree else tree
    block = p["h"]["block"]

    def of(*path):
        parts = []
        for j in range(per):
            t = block[f"layer_{j}"]
            for key in path:
                t = t[key]
            parts.append(t)
        t = jnp.stack(parts, 1)                  # [periods, period, ...]
        return t.reshape((n * per,) + t.shape[2:])

    kv = of("attn", "kv_kernel")
    return {
        "wte": p["embed"]["tok"]["embedding"],
        "head_w": p["lm_head"]["kernel"], "lnf_g": p["ln_f"]["scale"],
        "ln1_g": of("ln1", "scale"), "ln2_g": of("ln2", "scale"),
        "q_w": of("attn", "q_kernel"),
        "kv_w": kv.reshape(kv.shape[:2] + (-1,)),
        "o_w": of("attn", "out", "kernel"),
        "router": of("moe", "router"), "e_gate": of("moe", "e_gate"),
        "e_up": of("moe", "e_up"), "e_down": of("moe", "e_down"),
    }


# -- the counts: operations and bytes from shapes -----------------------

def attention_params(cfg: dict) -> int:
    """q, k, v and o of one layer."""
    d, h, hk, dh = _dims(cfg)
    return 2 * d * h * dh + 2 * d * hk * dh


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["moe_num_primary_experts"]


def expert_params(cfg: dict) -> int:
    """Gate, up and down of one expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def active_layer_params(cfg: dict) -> int:
    """The matrices one token passes through in one layer: attention, the
    router and the chosen experts."""
    return (attention_params(cfg) + router_params(cfg)
            + cfg["moe_num_active_primary_experts"] * expert_params(cfg))


def total_params(cfg: dict) -> int:
    d, l = cfg["hidden_size"], cfg["num_hidden_layers"]
    layer = (attention_params(cfg) + router_params(cfg) + 2 * d
             + cfg["moe_num_primary_experts"] * expert_params(cfg))
    # the embedding and the untied head, and the last norm's gains
    return l * layer + 2 * d * cfg["vocab_size"] + d


def attended_rows(cfg: dict, context):
    """(rows of the full layers' pool, rows of the window layers' pool)
    one query attends, summed over the layers, when it attends `context`
    positions, itself included: a full layer `context` rows, a window
    layer `min(context, window)`."""
    ctx = np.asarray(context, np.float64)
    kinds = layer_kinds(cfg)
    n_win = sum(1 for _, w in kinds if w)
    win = float(cfg["sliding_window_size"])
    return (len(kinds) - n_win) * ctx, n_win * np.minimum(ctx, win)


def _row_flops(cfg: dict) -> float:
    """Scores and values of one attended row of one layer, all query
    heads."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]


def forward_flops_token(cfg: dict, context: int, head: bool) -> float:
    """One token's forward pass attending `context` positions (itself
    included), through the six experts it is routed to; `head` adds the
    vocabulary projection."""
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
    parameter: 16 of 64 experts a chip)."""
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
    """The experts of all layers: what a tick's `moe_experts_hit` can
    reach."""
    return cfg["num_hidden_layers"] * cfg["moe_num_primary_experts"]


def expert_bytes(cfg: dict) -> int:
    """One expert of one layer: the unit of the engine's
    `moe_experts_hit`, which sums the distinct experts a tick's live
    tokens chose over the layers (and `summary()` over the ticks)."""
    return expert_params(cfg) * ACT_BYTES


def dense_weight_bytes(cfg: dict) -> int:
    """Every matrix a tick reads whatever is routed: attention and the
    float32 router of every layer, and the head; the embedding is a
    gather of a few rows and is not."""
    return (cfg["num_hidden_layers"] * (
        attention_params(cfg) * ACT_BYTES + router_params(cfg) * 4)
        + cfg["hidden_size"] * cfg["vocab_size"] * ACT_BYTES)


def decode_weight_bytes(cfg: dict) -> int:
    """The weights of one tick if every expert of every layer is hit."""
    return dense_weight_bytes(cfg) + (
        cfg["num_hidden_layers"] * cfg["moe_num_primary_experts"]
        * expert_bytes(cfg))


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
    """What one tick has to read: the weights outside the experts once,
    the experts that a live token chose (`experts_hit`: distinct experts
    summed over the layers) and the rows its live streams attend."""
    return (dense_weight_bytes(cfg) + experts_hit * expert_bytes(cfg)
            + attended_bytes(cfg, contexts))
