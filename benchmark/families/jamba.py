"""The `jamba` family: everything of the benchmark that knows Jamba
(ai21labs/AI21-Jamba2-3B).

`cfg` is `benchmark/configs/jamba2-3b.json` as a dict: the published
`config.json` keys (`attn_layer_period`, `attn_layer_offset`, the
`mamba_*` sizes, `num_experts` 1, `tie_word_embeddings`), with the
deployment's own beside them (`served_positions`, the dtypes).

The reference half is the model as its config and its description give
it, in straightforward `jax.numpy`: a Python loop over the layers, each
walking the sequence in blocks of `BLOCK_ROWS` positions (the recurrence
by `lax.scan` over the positions of a block, carried from block to
block), no cache, no paging, no kernel. Sizes: width `d`, `H` query heads
over one key/value head of `d_h = d / H`, inner width `D = expand * d`,
`N` states a channel, a convolution of `K` taps, the step's rank `R`, a
dense SwiGLU of width `f` (`num_experts` 1), a head tied to the
embedding. Layer `i` is attention where `i % attn_layer_period ==
attn_layer_offset` (HF's `layers_block_type`), a Mamba-1 mixer elsewhere.
For layer `i` with input `x`:

- `u = RMS_1(x)` (`x / sqrt(mean(x^2) + eps) * g`).
- Attention: `q = W_q u`, `k = W_k u`, `v = W_v u`, no positional
  encoding at all (NoPE), every `s <= t`, scores scaled by `d_h^-1/2`,
  all query heads over the one key/value head; `x' = x + W_o attn`.
- Mamba: `[h, z] = W_in u`; `h = silu(conv_K(h) + b_conv)`, the causal
  depthwise convolution `sum_j w_j h_{t-K+1+j}` (zeros before the
  stream); `[delta, B, C] = W_x h`, each RMS-normed (Jamba's
  `dt_layernorm`, `b_layernorm`, `c_layernorm`, eps `rms_norm_eps`);
  `Delta = softplus(W_dt delta + b_dt)`, `A = -exp(A_log)`;
  `s_t = exp(Delta_t A) s_{t-1} + Delta_t B_t h_t` per channel and state
  (float32, from zeros); `y = (s_t . C_t + D h_t) * silu(z)`;
  `x' = x + W_out y`. No bias on a projection (`mamba_proj_bias`).
- `n = RMS_2(x')`; out `x' + W_down (silu(W_gate n) * W_up n)`.
- Head: `RMS_f(x) W_e^T`, the embedding's own rows.

Departures from the published model, each also a line of the
configuration's `assumed` or `not_built`:

- the config gives no head size: `d / H` = 128;
- the state and its update are float32 in every mode (the published
  kernels' `ssm_state` too); the matrices bfloat16, the norms' gains,
  `A_log`, `D`, `b_dt` and `b_conv` float32;
- weights from the seed (`make_weights`): Mamba's own initialisation of
  `A_log`, `D` and `b_dt`, PyTorch's of the convolution;
- memory: blocks of positions, each layer's matrices upcast once, and a
  dynamic count of blocks: `rows` asks for the logits of those positions
  alone, and a causal model needs no position past the last of them.

Besides the `smallthinker` family's members it counts what a mamba layer
moves: `state_bytes` (one layer's state of one stream: the float32 scan
state and the convolution's window), `scan_bytes` (what the `ssm_scan`
kernel reads and writes for a chunk of positions in every mamba layer:
`delta` and `u = delta h` read, `B` and `C` read, `y` written, float32,
and the state in and out), and `decode_tick_bytes` with the states a tick
moves beside the weights and the attention rows.

It imports nothing of the program; only `program_model` does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import HIGHEST, act, mm

ACT_BYTES = 2          # bf16: matrices, activations and rows as served
STATE_BYTES = 4        # float32: the scan's state
BLOCK_ROWS = 256       # positions of one block of the reference
#: leaves stacked by layer, by attention layer or by mamba layer
STACKED = ("ln1_g", "ln2_g", "wi", "wo", "q_w", "kv_w", "o_w", "in_w",
           "conv_w", "conv_b", "x_w", "dt_norm_g", "b_norm_g", "c_norm_g",
           "dt_w", "dt_b", "A_log", "D", "out_w")
MAMBA = ("in_w", "conv_w", "conv_b", "x_w", "dt_norm_g", "b_norm_g",
         "c_norm_g", "dt_w", "dt_b", "A_log", "D", "out_w")
ATTENTION = ("q_w", "kv_w", "o_w")
#: the faults the tests plant, each a reading of the model its config or
#: a served stream rules out: the state kept in bfloat16, the state or the
#: convolution's window not carried from one chunk of a prompt to the
#: next, the step, B and C not normed
FAULTS = ("bf16_state", "state_not_carried", "conv_not_carried",
          "no_dt_bc_norm")
_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


# -- sizes ----------------------------------------------------------------

def _dims(cfg: dict) -> tuple[int, int, int, int]:
    """(width, query heads, key/value heads, head size)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, h, cfg["num_key_value_heads"], d // h


def _mamba(cfg: dict) -> tuple[int, int, int, int]:
    """(inner width, states a channel, convolution taps, step rank)."""
    return (cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_dt_rank"])


def layer_kinds(cfg: dict) -> list[str]:
    """"attention" or "mamba" a layer (HF's `layers_block_type`)."""
    if cfg["num_experts"] != 1 or cfg["mamba_proj_bias"]:
        raise ValueError("the reference is written for dense SwiGLU layers "
                         "(num_experts 1) and projections without a bias")
    per, off = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return ["attention" if i % per == off else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def period(cfg: dict) -> tuple:
    """The program's `period`: ``"mamba"`` or a NoPE full layer ``(False,
    0)`` an entry, the shortest pattern the layers repeat."""
    kinds = [(False, 0) if k == "attention" else "mamba"
             for k in layer_kinds(cfg)]
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return tuple(kinds[:n])


def shapes(cfg: dict) -> dict:
    d, h, hk, dh = _dims(cfg)
    di, n, k, r = _mamba(cfg)
    kinds = layer_kinds(cfg)
    l, m, a = len(kinds), kinds.count("mamba"), kinds.count("attention")
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    return {
        "wte": (v, d), "lnf_g": (d,), "ln1_g": (l, d), "ln2_g": (l, d),
        "wi": (l, d, 2, f),              # [gate | up]
        "wo": (l, f, d),
        "q_w": (a, d, h * dh), "kv_w": (a, d, 2 * hk * dh),   # [k | v]
        "o_w": (a, h * dh, d),
        "in_w": (m, d, 2 * di),          # [h | z]
        "conv_w": (m, k, di), "conv_b": (m, di),
        "x_w": (m, di, r + 2 * n),       # [delta | B | C]
        "dt_norm_g": (m, r), "b_norm_g": (m, n), "c_norm_g": (m, n),
        "dt_w": (m, r, di), "dt_b": (m, di),
        "A_log": (m, n, di), "D": (m, di),
        "out_w": (m, di, d),
    }


def positions(cfg: dict) -> int:
    """The longest sequence served: what the serve reference pads to and
    the program's `max_seq_len`."""
    return int(cfg.get("served_positions", cfg["max_position_embeddings"]))


def make_weights(cfg: dict, seed) -> dict:
    """Weights from the seed, jittable (`seed` a uint32): matrices N(0,
    `initializer_range`) rounded to the configuration's `param_dtype`;
    in float32 the gains 1 + N(0, `initializer_range`), `A_log = log(1 ..
    N)` and `D = 1` a channel, `b_dt = softplus^-1(dt)` with `dt`
    log-uniform in [1e-3, 1e-1] (Mamba's own initialisation: a state that
    remembers over hundreds to thousands of positions), and the
    convolution's taps and bias U(-1/sqrt(K), 1/sqrt(K)) (PyTorch's
    `Conv1d`, fan-in K). The program is handed these leaves, so both
    sides hold the same rounded numbers."""
    std = float(cfg.get("initializer_range", 0.02))
    mat = _DTYPES[cfg.get("param_dtype", "bfloat16")]
    _, n, k, _ = _mamba(cfg)
    key = jax.random.key(seed)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        sub = jax.random.fold_in(key, i)
        if name == "A_log":
            out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                1, n + 1, dtype=jnp.float32))[:, None], shape)
        elif name == "D":
            out[name] = jnp.ones(shape, jnp.float32)
        elif name == "dt_b":
            lo, hi = np.log(1e-3), np.log(1e-1)
            dt = jnp.exp(jax.random.uniform(sub, shape, jnp.float32, lo, hi))
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif name in ("conv_w", "conv_b"):
            w = jax.random.uniform(sub, shape, jnp.float32, -k ** -0.5,
                                   k ** -0.5)
            out[name] = w if name == "conv_b" else w.astype(mat)
        else:
            w = std * jax.random.normal(sub, shape, jnp.float32)
            out[name] = 1.0 + w if name.endswith("_g") else w.astype(mat)
    return out


def compared_leaves(tree: dict) -> tuple[dict, set]:
    """Every leaf, those of `STACKED` one norm a layer (a training
    comparison would read them; no cell of this family trains)."""
    return dict(tree), set(STACKED)


# -- the reference: forward -------------------------------------------------

def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _up(w, mode: str):
    """A leaf as `mm` wants it: float32 for the reference (upcast once a
    layer), as stored otherwise (`mm` rounds it itself)."""
    return w.astype(jnp.float32) if mode == "f32" else w


def _block_rows(s: int, chunk: int | None) -> int:
    """The largest divisor of `s` that is at most BLOCK_ROWS (and divides
    `chunk`, where a chunk fault needs block edges at chunk edges)."""
    top = min(s, BLOCK_ROWS, chunk or s)
    return max(r for r in range(1, top + 1)
               if s % r == 0 and (chunk is None or chunk % r == 0))


def _mamba_block(cfg, mode, lp, u, carry, restart, fault):
    """One block of positions `u` [T, width] (normed) through a mamba
    mixer from `carry` (state [N, D] float32, the convolution's last K - 1
    inputs [K - 1, D]); `restart` zeroes the carry first (a chunk fault's
    edge)."""
    a = act(mode)
    f32 = jnp.float32
    di, n, k, r = _mamba(cfg)
    state, window = carry
    if fault == "state_not_carried":
        state = jnp.where(restart, 0.0, state)
    if fault == "conv_not_carried":
        window = jnp.where(restart, 0, window)
    t = u.shape[0]
    hz = mm(u, lp["in_w"], mode).astype(a)
    h, z = hz[:, :di], hz[:, di:]
    xs = jnp.concatenate([window, h]).astype(f32)
    conv = sum(xs[j:j + t] * lp["conv_w"][j].astype(f32)
               for j in range(k)) + lp["conv_b"]
    window = xs[t:].astype(a)
    h = jax.nn.silu(conv).astype(a)
    dbc = mm(h, lp["x_w"], mode).astype(f32)
    delta, bb, cc = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    if fault != "no_dt_bc_norm":
        eps = cfg["rms_norm_eps"]
        delta = _rms(delta, lp["dt_norm_g"], eps)
        bb, cc = _rms(bb, lp["b_norm_g"], eps), _rms(cc, lp["c_norm_g"], eps)
    step = jax.nn.softplus(mm(delta.astype(a), lp["dt_w"], mode).astype(f32)
                           + lp["dt_b"])                       # [T, D]
    decay = -jnp.exp(lp["A_log"])                               # [N, D]
    hf = h.astype(f32)

    def one(s, xs):
        d_t, u_t, b_t, c_t = xs
        s = jnp.exp(d_t[None] * decay) * s + b_t[:, None] * u_t[None]
        if fault == "bf16_state":
            s = s.astype(jnp.bfloat16).astype(f32)
        return s, (s * c_t[:, None]).sum(0)

    state, y = jax.lax.scan(one, state, (step, step * hf, bb, cc))
    y = (y + lp["D"] * hf) * jax.nn.silu(z.astype(f32))
    return mm(y.astype(a), lp["out_w"], mode).astype(a), (state, window)


def _attention_block(cfg, mode, lp, u, kv, t0):
    """One block of queries `u` [T, width] (normed) at positions `t0 +
    arange(T)`; their keys and values go into `kv` ([S, 2, K, d_h]) first
    and every key at or before the query is attended (keys past the block
    are still zeros: masked)."""
    a = act(mode)
    t = u.shape[0]
    _, h, hk, dh = _dims(cfg)
    q = mm(u, lp["q_w"], mode).astype(a).reshape(t, hk, h // hk, dh)
    new = mm(u, lp["kv_w"], mode).astype(a).reshape(t, 2, hk, dh)
    kv = jax.lax.dynamic_update_slice_in_dim(kv, new, t0, 0)
    scores = jnp.einsum("tkgd,skd->kgts", q, kv[:, 0], precision=HIGHEST,
                        preferred_element_type=jnp.float32) * dh ** -0.5
    live = jnp.arange(kv.shape[0])[None] <= (t0 + jnp.arange(t))[:, None]
    scores = jnp.where(live[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(a)
    ctx = jnp.einsum("kgts,skd->tkgd", p, kv[:, 1], precision=HIGHEST,
                     preferred_element_type=jnp.float32)
    return mm(ctx.astype(a).reshape(t, h * dh), lp["o_w"], mode).astype(a), kv


def _forward_one(cfg, p, tokens, mode, rows=None, fault=None, chunk=None):
    a = act(mode)
    eps = cfg["rms_norm_eps"]
    s = tokens.shape[0]
    d, _, hk, dh = _dims(cfg)
    di, _, k, _ = _mamba(cfg)
    n_state = cfg["mamba_d_state"]
    rows_t = _block_rows(s, chunk if fault in FAULTS[1:3] else None)
    # a causal model needs no position past the last asked for
    last = s if rows is None else jnp.max(rows) + 1
    blocks = (last + rows_t - 1) // rows_t
    x = p["wte"][tokens].astype(a)
    at = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(layer_kinds(cfg)):
        j = at[kind]
        at[kind] += 1
        lp = {"ln1_g": p["ln1_g"][i], "ln2_g": p["ln2_g"][i],
              "wi": _up(p["wi"][i], mode), "wo": _up(p["wo"][i], mode),
              **{name: _up(p[name][j], mode)
                 for name in (MAMBA if kind == "mamba" else ATTENTION)}}
        if kind == "mamba":
            carry = (jnp.zeros((n_state, di), jnp.float32),
                     jnp.zeros((k - 1, di), a))
        else:
            carry = jnp.zeros((s, 2, hk, dh), a)

        def block(b, state, lp=lp, kind=kind):
            x, carry = state
            t0 = b * rows_t
            xb = jax.lax.dynamic_slice_in_dim(x, t0, rows_t, 0)
            u = _rms(xb, lp["ln1_g"], eps).astype(a)
            if kind == "mamba":
                restart = (t0 % chunk == 0) if chunk else False
                out, carry = _mamba_block(cfg, mode, lp, u, carry, restart,
                                          fault)
            else:
                out, carry = _attention_block(cfg, mode, lp, u, carry, t0)
            xb = (xb + out).astype(a)
            nb = _rms(xb, lp["ln2_g"], eps).astype(a)
            gu = mm(nb, lp["wi"].reshape(d, -1), mode).astype(
                jnp.float32).reshape(rows_t, 2, -1)
            hdn = (jax.nn.silu(gu[:, 0]) * gu[:, 1]).astype(a)
            xb = (xb + mm(hdn, lp["wo"], mode).astype(a)).astype(a)
            return jax.lax.dynamic_update_slice_in_dim(x, xb, t0, 0), carry

        x, _ = jax.lax.fori_loop(0, blocks, block, (x, carry))
    if rows is not None:
        x = x[rows]
    x = _rms(x, p["lnf_g"], eps).astype(a)
    return mm(x, _up(p["wte"], mode).T, mode).astype(jnp.float32)


def forward(cfg: dict, params: dict, tokens, mode: str = "f32", rows=None,
            fault=None, chunk=None):
    """Logits [b, s, vocab] in float32, one sequence after another; with
    `rows` (positions, [r]) those positions' alone, [b, r, vocab]: no
    position past the last of them is computed. `fault` plants one of
    `FAULTS` (the tests'); the two that lose a carry lose it at every
    multiple of `chunk` positions."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    if fault in FAULTS[1:3] and not chunk:
        raise ValueError(f"the fault {fault!r} needs the chunk it is lost at")
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_forward_one(cfg, params, row, mode, rows, fault,
                                       chunk) for row in tokens])


# -- the program: its model object and its parameter tree ----------------

def program_model(cfg: dict, mix: dict):
    """The program's model of this configuration: the Llama dialect of
    `models/transformer.py` with a period of mamba and NoPE attention
    layers, a dense SwiGLU a layer and the head tied to the embedding.
    `quant` is "none" in every cell; the control switches the program's
    own int8 path on (`--set quant='"int8_fwd"'`)."""
    from pytorchdistributed_tpu.models.llama import Llama, llama_config

    d, h, hk, dh = _dims(cfg)
    di, n, k, r = _mamba(cfg)
    opts = {key: mix[key] for key in ("quant",) if key in mix}
    return Llama(llama_config(
        "test", vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], embed_dim=d, num_heads=h,
        num_kv_heads=hk, head_size=dh, mlp_dim=cfg["intermediate_size"],
        max_seq_len=positions(cfg), norm_eps=cfg["rms_norm_eps"],
        period=period(cfg), ssm_inner=di, ssm_state=n, ssm_conv=k,
        ssm_dt_rank=r, tie_embeddings=cfg["tie_word_embeddings"],
        fp32_logits=True,
        dtype=_DTYPES[cfg.get("compute_dtype", "bfloat16")],
        param_dtype=_DTYPES[cfg.get("param_dtype", "bfloat16")], **opts))


#: benchmark leaf -> (module, program leaf) inside a mamba layer's block
_MAMBA_LEAVES = {"in_w": "in_kernel", "conv_w": "conv_kernel",
                 "conv_b": "conv_bias", "x_w": "x_kernel",
                 "dt_norm_g": "dt_norm", "b_norm_g": "b_norm",
                 "c_norm_g": "c_norm", "dt_w": "dt_kernel",
                 "dt_b": "dt_bias", "A_log": "A_log", "D": "D",
                 "out_w": "out_kernel"}


def _layout(cfg: dict):
    """(entries a period, periods, for each entry its kind and its rank
    among the period's entries of that kind, the kinds' counts a
    period)."""
    per = period(cfg)
    kinds = ["mamba" if e == "mamba" else "attention" for e in per]
    ranks = [kinds[:j].count(kind) for j, kind in enumerate(kinds)]
    return len(per), cfg["num_hidden_layers"] // len(per), kinds, ranks


def to_program_tree(w: dict, cfg: dict, mix: dict) -> dict:
    """Benchmark layout (stacked by layer, or by layer of a kind) ->
    `Llama`'s `params` tree, as a loader of a published checkpoint would:
    the leaves as they are, grouped by module; layer ``p * period + j``
    is ``layer_<j>`` of the scanned period ``p``."""
    per, n, kinds, ranks = _layout(cfg)
    d, _, hk, dh = _dims(cfg)

    def of(name, j):
        t = w[name]
        if name in MAMBA or name in ATTENTION:
            count = kinds.count(kinds[j])
            return t.reshape((n, count) + t.shape[1:])[:, ranks[j]]
        return t.reshape((n, per) + t.shape[1:])[:, j]

    block = {}
    for j, kind in enumerate(kinds):
        layer = {"ln1": {"scale": of("ln1_g", j)},
                 "ln2": {"scale": of("ln2_g", j)},
                 "mlp": {"wi_kernel": of("wi", j),
                         "wo": {"kernel": of("wo", j)}}}
        if kind == "mamba":
            layer["mamba"] = {leaf: of(name, j)
                              for name, leaf in _MAMBA_LEAVES.items()}
        else:
            layer["attn"] = {
                "q_kernel": of("q_w", j),
                "kv_kernel": of("kv_w", j).reshape(n, d, 2, hk * dh),
                "out": {"kernel": of("o_w", j)}}
        block[f"layer_{j}"] = layer
    return {"params": {
        "embed": {"tok": {"embedding": w["wte"]}}, "h": {"block": block},
        "ln_f": {"scale": w["lnf_g"]}}}


def from_program_tree(tree: dict, cfg: dict, mix: dict) -> dict:
    """The inverse."""
    per, n, kinds, ranks = _layout(cfg)
    p = tree["params"] if "params" in tree else tree
    block = p["h"]["block"]

    def of(kind, *path):
        """[periods, entries of `kind`, ...] -> by layer of the kind."""
        parts = []
        for j in range(per):
            if kind not in (None, kinds[j]):
                continue
            t = block[f"layer_{j}"]
            for key in path:
                t = t[key]
            parts.append(t)
        t = jnp.stack(parts, 1)
        return t.reshape((n * len(parts),) + t.shape[2:])

    kv = of("attention", "attn", "kv_kernel")
    return {
        "wte": p["embed"]["tok"]["embedding"], "lnf_g": p["ln_f"]["scale"],
        "ln1_g": of(None, "ln1", "scale"), "ln2_g": of(None, "ln2", "scale"),
        "wi": of(None, "mlp", "wi_kernel"),
        "wo": of(None, "mlp", "wo", "kernel"),
        "q_w": of("attention", "attn", "q_kernel"),
        "kv_w": kv.reshape(kv.shape[:2] + (-1,)),
        "o_w": of("attention", "attn", "out", "kernel"),
        **{name: of("mamba", "mamba", leaf)
           for name, leaf in _MAMBA_LEAVES.items()},
    }


# -- the counts: operations and bytes from shapes -----------------------

def _per_layer(cfg: dict, names) -> int:
    return sum(int(np.prod(shapes(cfg)[name][1:])) for name in names)


def mamba_params(cfg: dict) -> int:
    """One mamba mixer: projections, convolution, step, norms, A and D."""
    return _per_layer(cfg, MAMBA)


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one attention layer."""
    return _per_layer(cfg, ATTENTION)


def mlp_params(cfg: dict) -> int:
    return _per_layer(cfg, ("wi", "wo"))


def total_params(cfg: dict) -> int:
    """Every layer with its two norms, the embedding (the head is the
    same matrix) and the last norm."""
    kinds = layer_kinds(cfg)
    d = cfg["hidden_size"]
    return (kinds.count("mamba") * mamba_params(cfg)
            + kinds.count("attention") * attention_params(cfg)
            + len(kinds) * (mlp_params(cfg) + 2 * d)
            + cfg["vocab_size"] * d + d)


def _matmul_params(cfg: dict) -> int:
    """The matrices one token's forward pass multiplies by, the head
    left out (the embedding is a gather of a row)."""
    d, _, _, _ = _dims(cfg)
    di, n, k, r = _mamba(cfg)
    kinds = layer_kinds(cfg)
    mamba = d * 2 * di + di * (r + 2 * n) + r * di + di * d
    return (kinds.count("mamba") * mamba
            + kinds.count("attention") * attention_params(cfg)
            + len(kinds) * mlp_params(cfg))


def _row_flops(cfg: dict) -> float:
    """Scores and values of one attended row of one attention layer, all
    query heads."""
    _, h, _, dh = _dims(cfg)
    return 4.0 * h * dh


def attention_layers(cfg: dict) -> int:
    return layer_kinds(cfg).count("attention")


def forward_flops_token(cfg: dict, context: int, head: bool) -> float:
    """One token's matrix products attending `context` positions (itself
    included) in each attention layer; `head` adds the vocabulary
    projection. The scan's elementwise work is the VPU's and is not
    counted against the MXU's peak."""
    f = (2.0 * _matmul_params(cfg)
         + _row_flops(cfg) * attention_layers(cfg) * float(context))
    if head:
        f += 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return f


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A whole prompt, causal, sampled from at its last position."""
    n = float(prompt_len)
    return (2.0 * _matmul_params(cfg) * n
            + _row_flops(cfg) * attention_layers(cfg) * n * (n + 1) / 2
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (3 x forward) per trained token; every
    position has a target. No cell trains this family (`not_built`)."""
    fwd = prefill_flops(cfg, seq_len) / seq_len
    return 3.0 * (fwd + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
                  * (1 - 1 / seq_len))


def train_attention_flops_per_seq(cfg: dict, seq_len: int) -> float:
    n = float(seq_len)
    return 3.0 * _row_flops(cfg) * attention_layers(cfg) * n * (n + 1) / 2


def row_bytes(cfg: dict) -> int:
    """A key and a value of every key/value head, in one attention
    layer."""
    _, _, hk, dh = _dims(cfg)
    return 2 * hk * dh * ACT_BYTES


def kv_bytes_per_position(cfg: dict) -> int:
    """Keys and values of one position over the attention layers: the
    rows a stream's cache grows by."""
    return attention_layers(cfg) * row_bytes(cfg)


def state_bytes(cfg: dict) -> int:
    """One mamba layer's state of one stream: the float32 scan state [N,
    D] and the convolution's last K - 1 inputs in bf16; the unit of the
    engine's `ssm_states_read` and `ssm_states_written`."""
    di, n, k, _ = _mamba(cfg)
    return n * di * STATE_BYTES + (k - 1) * di * ACT_BYTES


def stream_state_bytes(cfg: dict) -> int:
    """Every mamba layer's state of one stream, whatever its length."""
    return layer_kinds(cfg).count("mamba") * state_bytes(cfg)


def scan_bytes(cfg: dict, positions: int) -> int:
    """What the `ssm_scan` kernel reads and writes for one call over
    `positions` positions in every mamba layer: `delta` and `u = delta h`
    read ([positions, D] float32 each), `B` and `C` ([positions, N]
    float32 each), `y` written ([positions, D] float32), and the state
    [N, D] float32 read and written."""
    di, n, _, _ = _mamba(cfg)
    per_layer = (positions * (3 * di + 2 * n) + 2 * n * di) * STATE_BYTES
    return layer_kinds(cfg).count("mamba") * per_layer


def decode_weight_bytes(cfg: dict) -> int:
    """The weights one tick reads, each leaf in the type it is served in
    (`make_weights`: the matrices bf16; gains, `A_log`, `D`, `b_dt`,
    `b_conv` float32), the embedding once, as the head (a tick's embedding
    rows are a gather of a few)."""
    leaves = jax.eval_shape(lambda: make_weights(cfg, np.uint32(0)))
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in leaves.values())


def attended_bytes(cfg: dict, contexts) -> float:
    """The key and value rows the queries of live streams of `contexts`
    (positions attended, itself included) read in one tick: every
    attention layer the whole context."""
    ctx = np.asarray(contexts, np.float64)
    return float(ctx.sum()) * kv_bytes_per_position(cfg)


def decode_tick_bytes(cfg: dict, contexts, states: float) -> float:
    """What one tick has to read and write: the weights once, `states`
    layer states of one stream moved (the engine's `ssm_states_read` plus
    `ssm_states_written` of the tick: a live stream's state of each mamba
    layer read and written back) and the rows its live streams attend."""
    return (decode_weight_bytes(cfg) + states * state_bytes(cfg)
            + attended_bytes(cfg, contexts))
