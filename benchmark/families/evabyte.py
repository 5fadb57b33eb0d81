"""The `evabyte` family: everything of the benchmark that knows EvaByte.

`cfg` is `benchmark/configs/evabyte.json` as a dict: the published
`config.json` keys (`attention_class: "eva"`, `window_size`, `chunk_size`,
32 heads of 128 with as many key/value heads, SwiGLU, RoPE,
`norm_add_unit_offset`, `fp32_skip_add`, `fp32_logits`, `mixedp_attn`),
with the deployment's own beside them (`served_positions`, the dtypes,
the standard deviations the weights are drawn with).

The reference half is the model as its config and EVA's published form
(Zheng et al., "Efficient Attention via Control Variates", ICLR 2023, as
EvaByte uses it) give it, in straightforward `jax.numpy`: a loop over
windows, masks for the summaries, no cache, no paging, no kernel. Sizes:
width `d`, `H` heads of `d_h` (keys and values have `H` heads too),
window `W`, chunk `C`, no bias, untied head.

- Norm: `RMS(x) = x / sqrt(mean(x^2) + eps) * (1 + g)`
  (`norm_add_unit_offset`).
- Layer: `h <- h + Attn(RMS_1(h))`, `h <- h + W_down(silu(W_gate x) *
  (W_up x))` with `x = RMS_2(h)`. The residual stream is float32 in every
  mode (`fp32_skip_add`), the logits float32 (`fp32_logits`), attention's
  scores and softmaxes float32 (`mixedp_attn`); matmul operands are in
  the mode's type.
- Projections: `q_t, k_t, v_t` per head from `RMS_1(h_t)`; RoPE on `q` and
  `k`; `scale = d_h^-1/2`.
- Chunk `c` holds positions `C c .. C c + C - 1`; window `w` holds
  positions `W w .. W w + W - 1`, chunks `(W/C) w .. (W/C) w + W/C - 1`.
  Each layer has two learned vectors per head, `phi_h` and `mu_h` in
  R^{d_h} (`adaptive_phi`, `adaptive_mu_k`). Summary of chunk `c`, head
  `h`: `a_m = softmax over the chunk's C positions of (phi_h . k_m)`,
  `kbar_c = sum_m a_m k_m + mu_h`, `vbar_c = sum_m a_m v_m`.
- Output for the query at `t`, `w = floor(t / W)`: exact scores `s_j =
  scale * q_t . k_j` for `W w <= j <= t`; summary scores `r_c = scale *
  q_t . kbar_c` for every `c < (W/C) w`; one softmax over both: `o_t =
  (sum_j e^{s_j} v_j + sum_c e^{r_c} vbar_c) / (sum_j e^{s_j} + sum_c
  e^{r_c})`; then `W_o`. A query in window 0 sees no summary; a chunk of
  the query's own window is never seen as a summary.
- Head: next-byte logits `W_head RMS_f(h)`.

Departures from the published model, each also a line of the
configuration's `assumed` or `not_built`:

- RoPE is applied before a chunk is summarised and pairs dim `i` with `i +
  d_h/2`; `phi . k` is not scaled; the summaries are deterministic (no
  random features are drawn at inference);
- norms are worked out in float32 (`fp32_ln: false` would let them run in
  bfloat16);
- prediction heads 1-7 of `num_pred_heads` 8, and the self-speculative
  decoding they serve, are not built: the head is the next byte's;
- memory: `forward` walks a sequence a window of rows at a time (the
  projections, the attention and the feed-forward of those rows, then the
  window's summaries into a bank that later windows read under the mask
  `c < (W/C) w`), layer after layer. The matrices are bfloat16, the type
  the configuration states, and are upcast a layer at a time.

It imports nothing of the program; only `program_model` does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import HIGHEST, act, mm

ACT_BYTES = 2          # bf16: weights, activations and caches as served
STACKED = ("ln1_g", "ln2_g", "qkv_w", "o_w", "gu_w", "down_w", "phi", "mu")
_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


# -- sizes ----------------------------------------------------------------

def _dims(cfg: dict) -> tuple[int, int, int]:
    """(width, heads, head size)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg.get("num_key_value_heads", h) != h:
        raise ValueError("EVA summarises per head: keys and values have "
                         "as many heads as the queries")
    return d, h, d // h


def _win(cfg: dict) -> tuple[int, int]:
    """(window, chunk)."""
    w, c = int(cfg["window_size"]), int(cfg["chunk_size"])
    if w % c:
        raise ValueError(f"chunk_size {c} does not divide window_size {w}")
    return w, c


def shapes(cfg: dict) -> dict:
    d, h, dh = _dims(cfg)
    l, v, f = (cfg["num_hidden_layers"], cfg["vocab_size"],
               cfg["intermediate_size"])
    return {
        "wte": (v, d), "head_w": (d, v), "lnf_g": (d,),
        "ln1_g": (l, d), "ln2_g": (l, d),
        "qkv_w": (l, d, 3 * d),          # columns [q | k | v]
        "o_w": (l, d, d),
        "gu_w": (l, d, 2 * f),           # columns [gate | up]
        "down_w": (l, f, d),
        "phi": (l, h, dh), "mu": (l, h, dh),
    }


def positions(cfg: dict) -> int:
    """The longest sequence served: what the serve reference pads to and
    the program's `max_seq_len`."""
    return int(cfg.get("served_positions", cfg["max_position_embeddings"]))


def make_weights(cfg: dict, seed) -> dict:
    """Weights from the seed, jittable (`seed` a uint32): matrices N(0,
    `initializer_range`) rounded to the configuration's `param_dtype`, the
    norms' gains `g` N(0, `initializer_range`) about the unit offset that
    the norm adds, `phi` and `mu` N(0, `summary_init_std`), gains and
    summaries' vectors in float32. The program is handed these leaves, so
    both sides hold the same rounded numbers."""
    std = float(cfg.get("initializer_range", 0.02))
    sstd = float(cfg.get("summary_init_std", 0.1))
    mat = _DTYPES[cfg.get("param_dtype", "bfloat16")]
    key = jax.random.key(seed)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        small = name in ("phi", "mu")
        w = (sstd if small else std) * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = w if small or name.endswith("_g") else w.astype(mat)
    return out


def compared_leaves(tree: dict) -> tuple[dict, set]:
    """Every leaf, those of `STACKED` one norm a layer (a training
    comparison would read them; no cell of this family trains)."""
    return dict(tree), set(STACKED)


# -- the reference: forward -------------------------------------------------

def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (
        1.0 + g.astype(jnp.float32))


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


def summarise(k, v, phi, mu, chunk: int):
    """The chunks' summaries of `k`, `v` [s, heads, d] (s a multiple of
    `chunk`): ([s / chunk, heads, d], the same), in float32."""
    s, h, d = k.shape
    kc = k.astype(jnp.float32).reshape(s // chunk, chunk, h, d)
    vc = v.astype(jnp.float32).reshape(s // chunk, chunk, h, d)
    a = jax.nn.softmax(jnp.einsum("cmhd,hd->cmh", kc, phi,
                                  precision=HIGHEST), axis=1)
    kbar = jnp.einsum("cmh,cmhd->chd", a, kc, precision=HIGHEST) + mu
    vbar = jnp.einsum("cmh,cmhd->chd", a, vc, precision=HIGHEST)
    return kbar, vbar


def _layer(cfg, mode, x, lp, summaries: bool = True):
    """One layer over one sequence `x` [s, width] (float32, s a multiple
    of the window), a window of rows at a time. `summaries=False` leaves
    the summaries out of the softmax (a planted fault of the tests)."""
    a = act(mode)
    s, d = x.shape
    _, h, dh = _dims(cfg)
    win, chunk = _win(cfg)
    per = win // chunk                  # summaries a window
    n_win = s // win
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    scale = dh ** -0.5
    up = (lambda w: w.astype(jnp.float32)) if mode == "f32" else (
        lambda w: w)
    qkv_w, o_w, gu_w, down_w = (up(lp[k]) for k in (
        "qkv_w", "o_w", "gu_w", "down_w"))
    causal = jnp.tril(jnp.ones((win, win), bool))
    c_ids = jnp.arange(n_win * per)

    def window(bank, xs):
        w, xw = xs                                   # xw [win, width]
        kbank, vbank = bank                          # [n_win*per, h, dh]
        pos = w * win + jnp.arange(win)
        y = _rms(xw, lp["ln1_g"], eps).astype(a)
        qkv = mm(y, qkv_w, mode).astype(a).reshape(win, 3, h, dh)
        q = _rope(qkv[:, 0], pos, theta)
        k = _rope(qkv[:, 1], pos, theta)
        v = qkv[:, 2]
        exact = jnp.einsum("thd,jhd->htj", q, k, precision=HIGHEST,
                           preferred_element_type=jnp.float32) * scale
        exact = jnp.where(causal[None], exact, -jnp.inf)
        seen = (c_ids < per * w) & summaries         # finished windows
        summ = jnp.einsum("thd,chd->htc", q, kbank.astype(a),
                          precision=HIGHEST,
                          preferred_element_type=jnp.float32) * scale
        summ = jnp.where(seen[None, None], summ, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([exact, summ], -1), axis=-1)
        p = p.astype(a)
        ctx = (jnp.einsum("htj,jhd->thd", p[..., :win], v,
                          precision=HIGHEST,
                          preferred_element_type=jnp.float32)
               + jnp.einsum("htc,chd->thd", p[..., win:],
                            vbank.astype(a), precision=HIGHEST,
                            preferred_element_type=jnp.float32))
        xw = xw + mm(ctx.astype(a).reshape(win, d), o_w, mode).astype(
            jnp.float32)
        y = _rms(xw, lp["ln2_g"], eps).astype(a)
        gate, upp = jnp.split(mm(y, gu_w, mode).astype(jnp.float32), 2,
                              axis=-1)
        y = (jax.nn.silu(gate) * upp).astype(a)
        xw = xw + mm(y, down_w, mode).astype(jnp.float32)
        # this window's summaries, for the windows that follow
        kbar, vbar = summarise(k, v, lp["phi"], lp["mu"], chunk)
        bank = tuple(jax.lax.dynamic_update_slice_in_dim(
            t, u.astype(t.dtype), per * w, 0)
            for t, u in ((kbank, kbar), (vbank, vbar)))
        return bank, xw

    zero = jnp.zeros((n_win * per, h, dh), jnp.float32)
    _, out = jax.lax.scan(window, (zero, zero),
                          (jnp.arange(n_win), x.reshape(n_win, win, d)))
    return out.reshape(s, d)


def _forward_one(cfg, p, tokens, mode, summaries: bool = True):
    win, _ = _win(cfg)
    n = tokens.shape[0]
    pad = -n % win                       # causal: a padded tail moves nothing
    tokens = jnp.pad(tokens, (0, pad))
    x = p["wte"][tokens].astype(jnp.float32)

    def body(x, lp):
        return _layer(cfg, mode, x, lp, summaries), None

    x, _ = jax.lax.scan(body, x, {k: p[k] for k in STACKED})
    x = _rms(x[:n], p["lnf_g"], cfg["rms_norm_eps"]).astype(act(mode))
    head = p["head_w"].astype(jnp.float32) if mode == "f32" else p["head_w"]
    return mm(x, head, mode).astype(jnp.float32)


def forward(cfg: dict, params: dict, tokens, mode: str = "f32",
            summaries: bool = True):
    """Logits [b, s, vocab] in float32, one sequence after another."""
    return jnp.stack([_forward_one(cfg, params, row, mode, summaries)
                      for row in tokens])


# -- the program: its model object and its parameter tree ----------------

def program_model(cfg: dict, mix: dict):
    """The program's model of this configuration: the Llama dialect of
    `models/transformer.py` with EVA as its attention kind. `quant` is
    "none" in every cell; the control switches the program's own int8
    path on (`--set quant='"int8_fwd"'`)."""
    from pytorchdistributed_tpu.models.llama import Llama, llama_config

    d, h, _ = _dims(cfg)
    win, chunk = _win(cfg)
    opts = {k: mix[k] for k in ("quant",) if k in mix}
    return Llama(llama_config(
        "test", vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], embed_dim=d, num_heads=h,
        num_kv_heads=None, mlp_dim=cfg["intermediate_size"],
        max_seq_len=positions(cfg), norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        eva_window=win, eva_chunk=chunk,
        norm_unit_offset=bool(cfg["norm_add_unit_offset"]),
        fp32_residual=bool(cfg["fp32_skip_add"]),
        fp32_logits=bool(cfg["fp32_logits"]),
        dtype=_DTYPES[cfg.get("compute_dtype", "bfloat16")],
        param_dtype=_DTYPES[cfg.get("param_dtype", "bfloat16")], **opts))


def to_program_tree(w: dict, cfg: dict, mix: dict) -> dict:
    """Benchmark layout (stacked by layer) -> `Llama`'s `params` tree, as
    a loader of a published checkpoint would: the leaves as they are,
    grouped by module."""
    d, _, _ = _dims(cfg)
    l, f = cfg["num_hidden_layers"], cfg["intermediate_size"]
    block = {
        "attn": {"qkv_kernel": w["qkv_w"].reshape(l, d, 3, d),
                 "out": {"kernel": w["o_w"]},
                 "eva_phi": w["phi"], "eva_mu": w["mu"]},
        "ln1": {"scale": w["ln1_g"]}, "ln2": {"scale": w["ln2_g"]},
        "mlp": {"wi_kernel": w["gu_w"].reshape(l, d, 2, f),
                "wo": {"kernel": w["down_w"]}},
    }
    return {"params": {
        "embed": {"tok": {"embedding": w["wte"]}}, "h": {"block": block},
        "ln_f": {"scale": w["lnf_g"]},
        "lm_head": {"kernel": w["head_w"]}}}


def from_program_tree(tree: dict, cfg: dict, mix: dict) -> dict:
    """The inverse."""
    d, _, _ = _dims(cfg)
    l, f = cfg["num_hidden_layers"], cfg["intermediate_size"]
    p = tree["params"] if "params" in tree else tree
    block = p["h"]["block"]
    return {
        "wte": p["embed"]["tok"]["embedding"],
        "head_w": p["lm_head"]["kernel"], "lnf_g": p["ln_f"]["scale"],
        "ln1_g": block["ln1"]["scale"], "ln2_g": block["ln2"]["scale"],
        "qkv_w": block["attn"]["qkv_kernel"].reshape(l, d, 3 * d),
        "o_w": block["attn"]["out"]["kernel"],
        "phi": block["attn"]["eva_phi"], "mu": block["attn"]["eva_mu"],
        "gu_w": block["mlp"]["wi_kernel"].reshape(l, d, 2 * f),
        "down_w": block["mlp"]["wo"]["kernel"],
    }


# -- the counts: operations and bytes from shapes -----------------------

def layer_matmul_params(cfg: dict) -> int:
    """q, k, v, o (width x width each) and gate, up, down."""
    d = cfg["hidden_size"]
    return 4 * d * d + 3 * d * cfg["intermediate_size"]


def matmul_params(cfg: dict) -> int:
    """The layers and the untied next-byte head; the embedding is a
    gather of a few rows."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    d, l = cfg["hidden_size"], cfg["num_hidden_layers"]
    # the embedding, the gains (two a layer and the last), phi and mu
    return (matmul_params(cfg) + d * cfg["vocab_size"] + (2 * l + 1) * d
            + 2 * l * d)


def attended_rows(cfg: dict, context):
    """(window rows, summary rows) a query attends in one layer when it
    attends `context` positions, itself included (the harness's count of
    a context: the query sits at position `context - 1`): the exact rows
    of its own window up to itself, and one summary a chunk of every
    finished window."""
    win, chunk = _win(cfg)
    p = np.asarray(context) - 1
    return p % win + 1, (win // chunk) * (p // win)


def _row_flops(cfg: dict) -> float:
    """Scores and values of one attended row, all heads, all layers."""
    return 4.0 * cfg["num_hidden_layers"] * cfg["hidden_size"]


def _summary_flops(cfg: dict) -> float:
    """A position's part of its chunk's summary, all layers: `phi . k`
    and the two weighted sums."""
    return 6.0 * cfg["num_hidden_layers"] * cfg["hidden_size"]


def forward_flops_token(cfg: dict, context: int, head: bool) -> float:
    """One token's forward pass attending `context` positions (itself
    included) through the window's rows and the summaries; `head` adds
    the next-byte projection."""
    wr, sr = attended_rows(cfg, context)
    f = (2.0 * cfg["num_hidden_layers"] * layer_matmul_params(cfg)
         + _row_flops(cfg) * float(wr + sr) + _summary_flops(cfg))
    if head:
        f += 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return f


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A whole prompt, causal, sampled from at its last position."""
    wr, sr = attended_rows(cfg, np.arange(1, prompt_len + 1,
                                          dtype=np.float64))
    return ((2.0 * cfg["num_hidden_layers"] * layer_matmul_params(cfg)
             + _summary_flops(cfg)) * prompt_len
            + _row_flops(cfg) * float(np.sum(wr + sr))
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (3 x forward) per trained token; every
    position has a target. No cell trains this family (16 bytes a
    parameter do not fit four layers)."""
    fwd = prefill_flops(cfg, seq_len) / seq_len
    return 3.0 * (fwd + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
                  * (1 - 1 / seq_len))


def train_attention_flops_per_seq(cfg: dict, seq_len: int) -> float:
    wr, sr = attended_rows(cfg, np.arange(1, seq_len + 1,
                                          dtype=np.float64))
    return 3.0 * _row_flops(cfg) * float(np.sum(wr + sr))


def row_bytes(cfg: dict) -> int:
    """A key and a value of every head, in one layer: what a window row
    and a summary row both are."""
    return 2 * cfg["hidden_size"] * ACT_BYTES


def kv_bytes_per_position(cfg: dict) -> int:
    """Exact keys and values of one position over all layers, while its
    window lasts (a finished window keeps one row of this size a chunk)."""
    return cfg["num_hidden_layers"] * row_bytes(cfg)


def decode_weight_bytes(cfg: dict) -> int:
    """What one tick has to read of the weights, once: every matmul
    weight, the head included."""
    return matmul_params(cfg) * ACT_BYTES


def decode_tick_bytes(cfg: dict, contexts) -> float:
    """What one tick has to move: the weights once and, a live stream of
    `contexts` (positions attended, itself included) a layer, the rows it
    attends (its window's, and the finished windows' summaries) and the
    one summary row written where the tick fills a chunk."""
    _, chunk = _win(cfg)
    ctx = np.asarray(contexts, np.int64)
    wr, sr = attended_rows(cfg, ctx) if ctx.size else (ctx, ctx)
    rows = float(np.sum(wr + sr)) + float(np.sum(ctx % chunk == 0))
    return (decode_weight_bytes(cfg)
            + rows * cfg["num_hidden_layers"] * row_bytes(cfg))
