"""Mixture-of-Experts — top-k routed expert FFNs over the "expert" mesh
axis (SURVEY.md §2c "EP"; the reference has no MoE content at all, so the
design is TPU-first rather than a port).

Two layers live here, and they differ in what happens to overflow:

  * `SwitchMoE` (Switch / GShard; `TransformerConfig.moe_*`) DROPS: every
    expert has a fixed capacity of slots, an assignment that loses the
    race for one skips the expert and rides the residual. Softmax scores,
    top-1 or top-2, GELU experts, all experts held, dense one-hot
    dispatch. The rest of this docstring describes it.
  * `DroplessMoE` (DeepSeek-V3 routing; `models/latent.py`'s config)
    drops NOTHING: sigmoid scores with a selection bias, top-k of any k,
    SwiGLU experts and shared experts, a SHARE of the experts held
    (`experts_held`), the assignments sorted by expert and multiplied as
    groups (`ops/grouped_matmul.py`). Its docstring says the rest.

For `SwitchMoE`, TPU-idiomatic expert parallelism is *not* a per-token
gather/scatter loop:

  * routing is computed densely (router logits → top-k → one-hot dispatch
    and combine tensors), so every shape is static and XLA can tile the
    whole thing onto the MXU;
  * tokens are routed in **G independent groups** with per-group capacity
    ``ceil(cf · (tokens/G)/experts)``. G defaults to one group per
    (data × fsdp × expert) mesh shard — the GShard layout in which the
    dispatch is a pure permutation of equal tiles, so it lowers to a
    literal ``all_to_all`` instead of the reduce-scatter a global
    capacity buffer forces. G = 1 (single-device / dp-only meshes)
    reproduces the original Switch global-capacity numerics exactly;
  * with an expert axis of size > 1 the dispatch/combine run through the
    EXPLICIT exchange (`ops/overlap.expert_a2a_ffn`): custom_vjp inside
    shard_map, chunked capacity pipelining of the combine a2a behind the
    next chunk's expert matmul, and int8 payloads under ``cfg.quant`` —
    2 a2a forward + 2 backward per MoE layer, all counted by the HLO
    census. Elsewhere (decode, pipeline bodies, non-tiling shapes) the
    dense einsum path runs and the auto-partitioner keeps its old job;
  * each expert processes a fixed capacity of slots; overflow tokens skip
    the expert and ride the residual connection (standard Switch
    behavior) — and the overflow FRACTION is sown into the diagnostics
    tables (``moe_overflow``, with the per-expert routing fractions as
    ``moe_frac``) instead of failing silently;
  * ``decode`` models route PER TOKEN (G = tokens, capacity 1): nothing
    ever overflows and a token's routing is independent of its slot
    neighbours, which is what keeps serving output bitwise-equal to
    offline ``generate()`` regardless of batch composition;
  * the Switch load-balancing auxiliary loss and the ST-MoE router
    z-loss are sown into the "losses" collection under distinct names;
    `training.losses.moe_token_cross_entropy_loss` applies each term's
    own weight.

References (PAPERS.md): Switch Transformer (Fedus et al.) for top-1 +
aux loss; GShard (Lepikhin et al.) for grouped dispatch + top-2; ST-MoE
(Zoph et al.) for the router z-loss.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from pytorchdistributed_tpu.ops.quant import absmax_scale, quantize
from pytorchdistributed_tpu.parallel.tp import Logical
from pytorchdistributed_tpu.runtime.mesh import Axis


def moe_groups_for(cfg, num_tokens: int, mesh=None) -> int:
    """The routing-group count G for this config/mesh/token count.

    decode → per-token groups (capacity never binds; serving stays
    bitwise vs `generate()`). An explicit ``cfg.moe_groups`` wins next
    (parity tests pin the sharded grouping on a single device with it).
    Auto (0): one group per (data × fsdp × expert) shard when the expert
    axis is real — the layout whose dispatch is a pure permutation —
    else 1, the original global-capacity numerics."""
    if cfg.decode:
        return num_tokens
    if cfg.moe_groups > 0:
        if num_tokens % cfg.moe_groups:
            raise ValueError(
                f"moe_groups {cfg.moe_groups} does not divide the "
                f"token count {num_tokens}")
        return cfg.moe_groups
    if mesh is None:
        from pytorchdistributed_tpu.parallel.overlap import _ambient_mesh

        mesh = _ambient_mesh()
    if mesh is not None and mesh.shape.get(Axis.EXPERT, 1) > 1:
        shards = (mesh.shape.get(Axis.DATA, 1)
                  * mesh.shape.get(Axis.FSDP, 1)
                  * mesh.shape[Axis.EXPERT])
        if num_tokens >= shards and num_tokens % shards == 0:
            return shards
    return 1


class SwitchMoE(nn.Module):
    """Drop-in MLP replacement: top-k routed expert FFNs.

    Call shape ``[batch, seq, embed] -> [batch, seq, embed]``. Expert
    kernels are stacked ``[experts, ...]`` with logical axis
    ``Logical.EXPERT`` so the rule tables shard them over the "expert"
    mesh axis.
    """

    cfg: "TransformerConfig"  # noqa: F821 — transformer.py's config
    deterministic: bool = True

    def _sow_moe_diagnostics(self, frac, overflow):
        """Routing health into the diagnostics tables (ISSUE 6 contract:
        gated entirely on the collection being mutable, so a
        diagnostics-off program's HLO is untouched): ``moe_frac`` — the
        per-expert first-choice routing fractions [e] (uniform = 1/e; a
        collapsing router shows up as one hot column), and
        ``moe_overflow`` — the fraction of routing assignments that lost
        the capacity race and rode the residual."""
        if self.is_initializing() or not self.is_mutable_collection(
                "diagnostics"):
            return
        self.sow("diagnostics", "moe_frac", frac)
        self.sow("diagnostics", "moe_overflow", overflow)

    def _use_a2a(self, mesh, num_groups: int, experts: int) -> bool:
        """Route dispatch/combine through the explicit a2a shard_map path
        (`ops/overlap.expert_a2a_ffn`)? Mirrors site_dot_general's
        gating: never under decode (per-token groups / single-chip) or
        inside a pipeline stage body (already a manual region), and only
        when the shapes tile the mesh — "a2a" intent still falls back
        rather than erroring, "dense" opts out (the bench A/B knob)."""
        cfg = self.cfg
        if cfg.moe_dispatch == "dense" or cfg.decode:
            return False
        if getattr(cfg, "pipeline_stages", 1) > 1:
            return False
        from pytorchdistributed_tpu.ops.overlap import expert_a2a_applicable

        return expert_a2a_applicable(num_groups, experts, mesh)

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        e, d, f = cfg.moe_experts, cfg.embed_dim, cfg.ffn_dim
        k = min(getattr(cfg, "moe_top_k", 1), e)
        b, s, _ = x.shape
        g = b * s  # token count
        from pytorchdistributed_tpu.parallel.overlap import _ambient_mesh

        mesh = _ambient_mesh()
        G = moe_groups_for(cfg, g, mesh)
        n = g // G  # tokens per routing group
        capacity = max(1, math.ceil(cfg.moe_capacity_factor * n / e))

        # -- router (fp32 for a stable softmax/top_k) --------------------
        router_kernel = self.param(
            "router",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02),
                (Logical.EMBED, Logical.EXPERT)),
            (d, e), jnp.float32)
        xg = x.reshape(G, n, d)
        xg = nn.with_logical_constraint(
            xg, (Logical.EGROUP, None, Logical.EMBED))
        logits = jnp.einsum("gnd,de->gne", xg.astype(jnp.float32),
                            router_kernel)
        probs = jax.nn.softmax(logits, axis=-1)

        # top-k choices. lax.top_k breaks probability ties toward the
        # LOWER expert index — deterministic, unlike a sort on floats.
        gate, idx = lax.top_k(probs, k)                     # [G, n, k]
        if k > 1:
            # GShard-style renormalization over the chosen pair; k=1
            # keeps the raw top probability (the Switch gate) so the
            # original top-1 numerics are untouched.
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # [G, n, k, e]

        # Switch aux loss on FIRST choices: e · Σ_e frac_e · mean_prob_e.
        # Minimized (=1) at uniform routing; sown for the loss fn to add.
        frac = onehot[:, :, 0, :].mean((0, 1))
        aux = e * jnp.sum(frac * probs.mean((0, 1)))
        self.sow("losses", "moe_aux", aux)
        # ST-MoE router z-loss: mean(logsumexp(logits)²) keeps router
        # logits small/stable. Sown under its own name — the loss fn
        # separates it from the aux leaves and applies its own weight.
        zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        self.sow("losses", "moe_zloss", zloss)

        # -- capacity assignment: each choice takes its expert's next
        # free slot, in K-MAJOR priority order — the [G, k·n] flatten
        # puts EVERY token's first choice ahead of ANY second choice, so
        # the cumsum race is deterministic and top-1 traffic can never be
        # displaced by top-2 spillover (GShard's ordering).
        oh = onehot.transpose(0, 2, 1, 3).reshape(G, k * n, e)
        pos = jnp.sum(jnp.cumsum(oh, axis=1) * oh,
                      axis=-1).astype(jnp.int32) - 1        # [G, k·n]
        kept = (pos < capacity).astype(jnp.float32)         # overflow→residual
        disp = (oh * kept[..., None])[..., None] * jax.nn.one_hot(
            pos, capacity, dtype=jnp.float32)[:, :, None, :]
        disp = disp.reshape(G, k, n, e, capacity)
        dispatch = jnp.sum(disp, axis=1)                    # [G, n, e, c]
        combine = jnp.sum(
            disp * gate.transpose(0, 2, 1)[..., None, None], axis=1)

        # the overflow fraction, surfaced instead of silently riding the
        # residual: 1 − (assignments that won a slot) / (all assignments)
        overflow = 1.0 - jnp.sum(oh * kept[..., None]) / (G * k * n)
        self._sow_moe_diagnostics(frac, overflow)

        # -- expert FFNs on [e, c, d] slots ------------------------------
        wi = self.param(
            "wi",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02),
                (Logical.EXPERT, Logical.EMBED, Logical.MLP)),
            (e, d, f), cfg.param_dtype)
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02),
                (Logical.EXPERT, Logical.MLP, Logical.EMBED)),
            (e, f, d), cfg.param_dtype)

        if self._use_a2a(mesh, G, e):
            from pytorchdistributed_tpu.ops.overlap import expert_a2a_ffn

            out = expert_a2a_ffn(
                xg.astype(cfg.dtype), dispatch.astype(cfg.dtype),
                combine.astype(cfg.dtype), wi.astype(cfg.dtype),
                wo.astype(cfg.dtype), mesh=mesh,
                quant=None if cfg.quant == "none" else cfg.quant,
                chunks=getattr(cfg, "moe_chunks", 1),
                gelu_approx=cfg.gelu_approximate,
                preferred_element_type=cfg.dtype)
        else:
            slots = jnp.einsum("gnec,gnd->gecd", dispatch.astype(cfg.dtype),
                               xg.astype(cfg.dtype))
            slots = nn.with_logical_constraint(
                slots, (None, Logical.EXPERT, None, Logical.EMBED))
            h = nn.gelu(
                jnp.einsum("gecd,edf->gecf", slots, wi.astype(cfg.dtype)),
                approximate=cfg.gelu_approximate)
            h = nn.with_logical_constraint(
                h, (None, Logical.EXPERT, None, Logical.MLP))
            out_slots = jnp.einsum("gecf,efd->gecd", h, wo.astype(cfg.dtype))
            out = jnp.einsum("gnec,gecd->gnd", combine.astype(cfg.dtype),
                             out_slots)
        out = out.reshape(g, d)
        if cfg.dropout_rate > 0:
            out = nn.Dropout(cfg.dropout_rate)(
                out, deterministic=self.deterministic)
        return out.reshape(b, s, d)


def _int8_rounded(x, axis):
    """`x` rounded to symmetric int8 along `axis` and back: what the int8
    contraction multiplies, for a contraction (`lax.ragged_dot`) that the
    injectable dot_general cannot stand in for."""
    scale = absmax_scale(x, (axis,))
    return (quantize(x, scale).astype(jnp.float32) * scale).astype(x.dtype)


#: `DroplessMoE`'s three banks of expert matrices, a leaf each
BANKS = ("e_gate", "e_up", "e_down")

#: what `DroplessMoE` counts a call, in the order a model puts them into
#: its "counters" vector
DROPLESS_COUNTERS = ("moe_assignments_held", "moe_assignments_total",
                     "moe_experts_hit", "moe_load_max", "moe_load_mean",
                     "moe_dropped")


def stack_hands_banks(cfg, banks) -> bool:
    """Whether a scanned stack of ``cfg`` hands `DroplessMoE` its
    ``banks`` (any tree of them) whole: not under ``quant``, which rounds
    the weights a bank at a time, and only banks kept in the type the
    products multiply in (the cast would be a copy of the whole stack)."""
    from pytorchdistributed_tpu.models.transformer import _cfg_dot_general

    return _cfg_dot_general(cfg) is None and all(
        leaf.dtype == cfg.dtype for leaf in jax.tree.leaves(banks))


def banks_read(cfg, params) -> str | None:
    """`ServingEngine.summary()`'s ``expert_banks``: how the programs of
    ``cfg`` over ``params`` read `DroplessMoE`'s banks. ``"in_place"``:
    the kernel of `ops/grouped_matmul.py` fetches each expert hit from
    where it lies, in a scanned stack's leaf or in a layer's own.
    ``"sliced"``: `lax.ragged_dot` on the bank's slice (off a TPU), or a
    scanned stack whose body slices its step's banks out first
    (`stack_hands_banks`). None: no such experts."""
    if not getattr(cfg, "router_experts", 0):
        return None
    in_place = jax.default_backend() == "tpu"
    if in_place and getattr(cfg, "scan_layers", False):
        in_place = stack_hands_banks(cfg, [
            leaf for path, leaf in jax.tree_util.tree_leaves_with_path(params)
            if getattr(path[-1], "key", None) in BANKS])
    return "in_place" if in_place else "sliced"


class DroplessMoE(nn.Module):
    """An expert layer that drops nothing and holds a SHARE of its
    experts (the layer `SwitchMoE` is not: that one races for capacity
    and drops the overflow). Under ``moe_scoring = "sigmoid"``
    DeepSeek-V3's routing: ``s = sigmoid(W_r x)``
    in float32, the ``experts_per_token`` largest of ``s + b`` (``b`` the
    stored selection bias; one group), weights ``s_e / sum(chosen s)``
    times ``routed_scale``, SwiGLU experts, plus ``shared_experts``
    SwiGLUs every token passes through. Under ``"softmax"``
    (SmallThinker's) the largest logits are chosen, with no bias, and
    weighed by a softmax over the chosen (which is a softmax over all,
    renormalised over them). ``moe_activation`` is the gate's:
    ``"silu"``, or ``"relu"`` (ReGLU: ``relu(gate) * up``).
    ``shared_experts`` may be 0. ``route`` [b, s, d], where given, is
    what the router reads in place of ``x`` (a router placed before
    attention reads that sublayer's input; the experts read ``x``).

    The router keeps its published width ``router_experts``; this layer
    holds experts ``experts_held = [lo, hi)`` of them and computes their
    part of the result for the tokens routed to them: the assignments are
    sorted by expert and the held ones go through one grouped matrix
    product a projection (`ops/grouped_matmul.py:grouped_product`, groups
    = the held experts' token counts: on a TPU a kernel that reads each
    expert hit from the bank where it lies, elsewhere `lax.ragged_dot`).
    The buffer is as long as every assignment, so no imbalance can
    overflow it. ``banks``, where given, is ``(leaves, step)``: the
    three banks (`BANKS`) of a scanned stack's every step as one ``[steps
    * held, ...]`` leaf each (`TransformerStack._expert_banks`), of which
    this call's experts are the groups from ``step * held`` on; the
    module's own parameters, the scan's slice of the same leaves, are
    then not read. What the absent experts would add is left
    out (expert parallelism without its exchange: on one chip there is
    nobody to exchange with), and nothing stands in for them.

    ``cfg`` needs ``embed_dim, moe_dim, router_experts, experts_held,
    experts_per_token, shared_experts, norm_topk_prob, routed_scale,
    moe_scoring, moe_activation, dtype, param_dtype, quant``
    (`models/latent.py:LatentConfig`, or `TransformerConfig` with
    ``router_experts > 0``). Call ``[b, s, d] -> ([b, s, d],
    counters)``; ``live [b, s]`` says which tokens the counters count
    (every token is computed): assignments to held experts and in all,
    distinct held experts hit, the busiest held expert's tokens, the mean
    over the held experts, and assignments to a held expert whose row of
    the sorted buffer does not lie in that expert's group of the grouped
    product (read from the permutation the combine gathers by and the
    group sizes the product is given; 0 unless the dispatch is at fault).
    """

    cfg: "LatentConfig | TransformerConfig"  # noqa: F821

    @nn.compact
    def __call__(self, x, live=None, route=None, banks=None):
        from pytorchdistributed_tpu.models.transformer import (
            _cfg_dot_general,
        )
        from pytorchdistributed_tpu.ops.grouped_matmul import grouped_product

        cfg = self.cfg
        d, f, e_pub = cfg.embed_dim, cfg.moe_dim, cfg.router_experts
        lo, hi = cfg.experts_held
        held, k = hi - lo, cfg.experts_per_token
        b, s, _ = x.shape
        t = b * s
        init = nn.initializers.normal(stddev=0.02)
        pd = cfg.param_dtype

        sigmoid = cfg.moe_scoring == "sigmoid"
        gate_act = nn.silu if cfg.moe_activation == "silu" else nn.relu
        router = self.param("router", init, (d, e_pub), jnp.float32)
        if sigmoid:
            bias = self.param("router_bias", nn.initializers.zeros_init(),
                              (e_pub,), jnp.float32)
        e_gate = self.param("e_gate", init, (held, d, f), pd)
        e_up = self.param("e_up", init, (held, d, f), pd)
        e_down = self.param("e_down", init, (held, f, d), pd)
        first = 0
        if banks is not None:
            # a scanned stack's banks, whole: this step's experts lie
            # from group step * held on, and the scan's slice is unused
            stack, step = banks
            e_gate, e_up, e_down = (stack[name] for name in BANKS)
            first = step * held
        if cfg.shared_experts:
            fs = f * cfg.shared_experts
            s_gate = self.param("s_gate", init, (d, fs), pd)
            s_up = self.param("s_up", init, (d, fs), pd)
            s_down = self.param("s_down", init, (fs, d), pd)

        xt = x.reshape(t, d).astype(cfg.dtype)
        rt = xt if route is None else route.reshape(t, d).astype(cfg.dtype)
        # -- routing, in float32 as published -------------------------
        logits = jnp.matmul(rt.astype(jnp.float32), router,
                            precision=lax.Precision.HIGHEST)  # [t, e_pub]
        if sigmoid:
            score = jax.nn.sigmoid(logits)
            _, chosen = lax.top_k(score + bias, k)          # [t, k]
            picked = jnp.take_along_axis(score, chosen, -1)
            if cfg.norm_topk_prob:
                picked = picked / picked.sum(-1, keepdims=True)
        else:
            picked, chosen = lax.top_k(logits, k)
            # over the chosen alone this is the softmax over all,
            # renormalised over them
            picked = jax.nn.softmax(picked, -1)
        weight = picked * cfg.routed_scale

        # -- the held experts' part: sort, group, multiply -------------
        flat = chosen.reshape(t * k)
        mine = (flat >= lo) & (flat < hi)
        group = jnp.where(mine, flat - lo, held)   # the others sort last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=held + 1)[:held].astype(
            jnp.int32)
        rows = xt[order // k]                               # [t*k, d]
        quant = _cfg_dot_general(cfg) is not None
        if quant:
            rows = _int8_rounded(rows, 1)

        def grouped(lhs, w):
            w = w.astype(cfg.dtype)
            if quant:
                w = _int8_rounded(w, 1)
            return grouped_product(lhs, w, sizes, first)

        h = (gate_act(grouped(rows, e_gate))
             * grouped(rows, e_up)).astype(cfg.dtype)
        if quant:
            h = _int8_rounded(h, 1)
        y = grouped(h, e_down)                              # [t*k, d] f32
        computed = jnp.arange(t * k) < sizes.sum()
        y = jnp.where(computed[:, None],
                      y * weight.reshape(t * k)[order][:, None], 0.0)
        # back into the tokens' order (a gather; every assignment has a
        # row, the absent experts' rows are nought) and summed a token
        row_of = jnp.argsort(order)         # an assignment's buffer row
        routed = y[row_of].reshape(t, k, d).sum(1)

        # -- the shared expert, on every token -------------------------
        dg = _cfg_dot_general(cfg, lax.dot_general)
        dims = (((1,), (0,)), ((), ()))

        def dense(lhs, w):
            return dg(lhs, w.astype(cfg.dtype), dims,
                      preferred_element_type=jnp.float32)

        if cfg.shared_experts:
            hs = (gate_act(dense(xt, s_gate)) * dense(xt, s_up)).astype(
                cfg.dtype)
            routed = routed + dense(hs, s_down)
        out = routed.astype(cfg.dtype)

        # -- what the tick brings back ---------------------------------
        lv = (jnp.ones((t,), bool) if live is None
              else live.reshape(t))
        mine_live = mine & jnp.repeat(lv, k)
        load = jnp.bincount(jnp.where(mine_live, flat - lo, held),
                            length=held + 1)[:held]
        n_held = mine_live.sum()
        counters = {
            "moe_assignments_held": n_held,
            "moe_assignments_total": lv.sum() * k,
            "moe_experts_hit": (load > 0).sum(),
            "moe_load_max": load.max(),
            "moe_load_mean": n_held / held,
            "moe_dropped": (mine_live & (jnp.searchsorted(
                jnp.cumsum(sizes), row_of, side="right") != flat - lo)
                            ).sum(),
        }
        return out.reshape(b, s, d), {
            n: v.astype(jnp.float32) for n, v in counters.items()}
