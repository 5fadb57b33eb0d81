"""The engine serves from weights in the compute type and the served
layout (serving/weights.py, `ServingEngine.set_params`).

A model with bfloat16 compute and float32 parameters casts every matrix
it multiplies by inside the program; the engine now makes that cast once,
where it takes a tree. The bar: tokens and logits are BITWISE what the
float32 tree gives (the old behaviour, called directly with the uncast
tree, is the reference), no leaf the model reads in float32 is narrowed,
the compiled tick converts no parameter, a swap of weights re-casts
without a retrace, and a tree that is stored in the compute type passes
through untouched but for a scanned stack's fused kernels, which the
engine holds as planes: tokens and logits bitwise the checkpoint
layout's, in either precision, under int8_fwd too, and a tree of either
layout is taken without a retrace.
"""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from pytorchdistributed_tpu.inference import generate, make_draft
from pytorchdistributed_tpu.models import GPT2, Llama, gpt2_config
from pytorchdistributed_tpu.models import llama_config
from pytorchdistributed_tpu.serving import ServingEngine
from pytorchdistributed_tpu.serving import engine as serving_engine
from pytorchdistributed_tpu.serving.engine import (
    nan_params,
    paged_decode_tick,
    paged_tick_logits,
)
from pytorchdistributed_tpu.serving import weights as served_weights
from pytorchdistributed_tpu.serving.weights import cast_only, served

BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
LENS, NEWS = (5, 13, 3, 9), (6, 4, 8, 5)


def _cfg(**kw):
    return gpt2_config("test", num_layers=2, max_seq_len=64,
                       dtype=jnp.bfloat16, param_dtype=jnp.float32, **kw)


def _init(model, seed=1):
    return model.init(jax.random.key(seed),
                      jnp.zeros((1, 4), jnp.int32))["params"]


def _prompts(cfg, lens=LENS):
    rng = np.random.default_rng(0)
    return [rng.integers(0, cfg.vocab_size, (m,)).astype(np.int32)
            for m in lens]


def _paths(tree, dtype):
    return [jax.tree_util.keystr(path)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
            if leaf.dtype == dtype]


def _is_norm(path: str) -> bool:
    return any(f"['{name}']" in path for name in ("ln1", "ln2", "ln_f"))


def _engine(kind: str, model, params):
    """The three ways the engine runs a model: paged, dense, and paged
    with a one-layer draft that carries proposal heads."""
    if kind == "dense":
        return ServingEngine(model, params, num_slots=3, prefill_bucket=16)
    kw = {}
    if kind == "spec":
        draft, dp = make_draft(
            GPT2(dataclasses.replace(model.cfg, decode=True)), params,
            num_layers=1, spec_heads=2)
        kw = dict(spec_k=3, draft_config=draft.cfg, draft_params=dp)
    return ServingEngine(model, params, num_slots=3, prefill_bucket=16,
                         block_size=8, **kw)


def _tick_logits(engine, weights):
    """Every slot's logits of one tick at the engine's own operands, for
    a tree of weights: the model's part of the tick, nothing donated."""
    tokens = jnp.asarray(engine._tokens)
    if engine.paged:
        fn = jax.jit(paged_tick_logits, static_argnums=0)
        return fn(engine._tick_model, weights, engine._cache,
                  engine._device_tables(), jnp.asarray(engine._lengths),
                  tokens)[0]
    return jax.jit(lambda w, c: engine._tick_model.apply(
        {"params": w, "cache": c}, tokens[:, None],
        mutable=["cache"])[0])(weights, engine._cache)


# ---------------------------------------------------------------------------
# (a), (b): tokens and logits are bitwise the float32 tree's


@pytest.mark.parametrize("kind", ["paged", "dense", "spec"])
def test_tokens_and_tick_logits_bitwise_float32_tree(kind):
    cfg = _cfg()
    model = GPT2(cfg)
    params = _init(model)
    engine = _engine(kind, model, params)
    assert _paths(params, F32) and not _paths(params, BF16)
    assert all(_is_norm(p) for p in _paths(engine._weights, F32))
    assert _paths(engine._weights, BF16)
    prompts = _prompts(cfg)
    reqs = []
    for p, n in zip(prompts, NEWS):
        reqs.append(engine.submit(p, max_new_tokens=n))
        engine.step()
    # mid-stream, slots live at ragged lengths: the tick's logits
    live = _tick_logits(engine, engine._weights)
    np.testing.assert_array_equal(
        np.asarray(live, np.float32),
        np.asarray(_tick_logits(engine, params), np.float32))
    assert np.isfinite(np.asarray(live, np.float32)).all()
    engine.run_until_idle()
    dm = GPT2(dataclasses.replace(cfg, decode=True))
    for p, n, r in zip(prompts, NEWS, reqs):
        ref = generate(dm, {"params": params}, jnp.asarray(p)[None],
                       max_new_tokens=n)
        np.testing.assert_array_equal(r.output_ids, np.asarray(ref)[0],
                                      err_msg=f"request {r.id}")
    if kind == "spec":
        # the draft is held the same way, its heads' kernels included
        left = _paths(engine._draft_weights, F32)
        assert left and all(_is_norm(p) for p in left)
        assert any("heads" in p for p in _paths(engine._draft_weights,
                                                BF16))
    engine.close()


# ---------------------------------------------------------------------------
# (c): the compiled tick converts no parameter


def _param_converts(text: str, floor: int) -> list[str]:
    """The float32 parameters of `main` with more than `floor` numbers
    that the program casts to bfloat16 (StableHLO text)."""
    sig = text[text.index("func.func public @main("):]
    sig = sig[:sig.index("{\n")]
    wide = set()
    for arg, shape in re.findall(r"(%arg\d+): tensor<([0-9x]*)xf32>", sig):
        if np.prod([int(d) for d in shape.split("x") if d]) > floor:
            wide.add(arg)
    return [arg for arg in re.findall(
        r"stablehlo\.convert (%arg\d+) : \(tensor<[0-9x]*xf32>\) -> "
        r"tensor<[0-9x]*xbf16>", text) if arg in wide]


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["scanned", "unrolled"])
def test_lowered_tick_converts_no_float32_parameter(scan_layers):
    cfg = _cfg(scan_layers=scan_layers)
    model = GPT2(cfg)
    params = _init(model)
    engine = ServingEngine(model, params, num_slots=3, prefill_bucket=16,
                           block_size=8)
    gain = cfg.num_layers * cfg.embed_dim   # a stacked norm's gain
    assert _param_converts(engine.lower_tick().as_text(), gain) == []
    # a scanned stack's fused q/k/v is re-laid as planes, an unrolled
    # stack's layers are left as they are
    qkv = 3 * cfg.num_layers * cfg.embed_dim ** 2 * 2       # bf16
    assert engine.summary()["weight_bytes_relaid"] == (
        qkv if scan_layers else 0)
    # the same tick over the float32 tree does convert them: the pattern
    # sees what it is meant to see
    _, args = engine._tick_program()
    old = paged_decode_tick.lower(engine._tick_model, params, *args[1:],
                                  candidates=engine.candidates).as_text()
    assert len(_param_converts(old, gain)) >= 2   # the table, its tied head
    # every leaf left in float32 is a norm's, and every norm's leaf is
    left = _paths(engine._weights, F32)
    assert left and all(_is_norm(p) for p in left)
    assert not any(_is_norm(p) for p in _paths(engine._weights, BF16))
    engine.close()


# ---------------------------------------------------------------------------
# (d): a swap re-casts and retraces nothing; a tree in the compute type
# passes through


def test_set_params_recasts_without_retrace():
    cfg = _cfg()
    model = GPT2(cfg)
    first, second = _init(model, 1), _init(model, 2)
    dm = GPT2(dataclasses.replace(cfg, decode=True))
    engine = ServingEngine(model, first, num_slots=3, prefill_bucket=16,
                           block_size=8)
    engine.warmup(prompt_lens=(8, 16))
    prompts = _prompts(cfg)

    def served():
        reqs = [engine.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, NEWS)]
        engine.run_until_idle()
        return [r.output_ids for r in reqs]

    def reference(tree):
        return [np.asarray(generate(dm, {"params": tree},
                                    jnp.asarray(p)[None],
                                    max_new_tokens=n))[0]
                for p, n in zip(prompts, NEWS)]

    for got, ref in zip(served(), reference(first)):
        np.testing.assert_array_equal(got, ref)
    traces = dict(serving_engine.TRACE_COUNTS)
    engine.set_params({"params": second})
    engine.invalidate_prefix_cache()   # blocks the first weights wrote
    assert all(_is_norm(p) for p in _paths(engine._weights, F32))
    out = served()
    for got, ref in zip(out, reference(second)):
        np.testing.assert_array_equal(got, ref)
    assert any((a != b).any() for a, b in zip(out, reference(first)))
    assert dict(serving_engine.TRACE_COUNTS) == traces
    s = engine.summary()
    norms = sum(leaf.nbytes for path, leaf in
                jax.tree_util.tree_leaves_with_path(second)
                if _is_norm(jax.tree_util.keystr(path)))
    whole = sum(leaf.nbytes for leaf in jax.tree.leaves(second))
    assert s["weight_bytes_cast"] == whole - norms
    assert s["weight_bytes_served"] == (whole - norms) // 2 + norms
    # the checkpoint's fused q/k/v, cast and re-laid as planes
    assert s["weight_bytes_relaid"] == _by_path(second)[
        QKV + ".value"].nbytes // 2
    # the engine's own tree handed back (the chaos path's restore, a
    # sibling replica's tree) is held as it is: its planes are taken as
    # planes, and neither layout retraces
    held = engine._weights
    engine.set_params(held)
    assert all(a is b for a, b in zip(jax.tree.leaves(engine._weights),
                                      jax.tree.leaves(held)))
    assert engine.summary()["weight_bytes_cast"] == 0
    assert engine.summary()["weight_bytes_relaid"] == 0
    assert dict(serving_engine.TRACE_COUNTS) == traces
    engine.close()


def _by_path(tree) -> dict:
    return {jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


QKV = "['h']['block']['attn']['qkv_kernel']"


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16-leaves", "f32-compute"])
def test_tree_in_the_compute_type_is_served_as_it_came(dtype):
    """bfloat16 leaves under bfloat16 compute, float32 under float32:
    nothing is wider than the compute type, so nothing is traced or cast,
    and every leaf the engine holds is the very array it was given but
    the scanned stack's fused q/k/v kernel, which it holds as planes
    under their own name: the same numbers, `[layers, 3, embed, width]`
    (serving/weights.py:served)."""
    cfg = dataclasses.replace(_cfg(), dtype=dtype, param_dtype=dtype)
    model = GPT2(cfg)
    params = _init(model)
    engine = ServingEngine(model, params, num_slots=2, prefill_bucket=16,
                           block_size=8)
    given, held = _by_path(params), _by_path(engine._weights)
    kernel = given[QKV + ".value"]                        # boxed by init
    planes = QKV.replace("qkv_kernel", "qkv_planes")
    assert set(given) - set(held) == {QKV + ".value"}
    assert set(held) - set(given) == {planes}
    assert all(held[k] is given[k] for k in held if k != planes)
    np.testing.assert_array_equal(held[planes],
                                  np.moveaxis(np.asarray(kernel), 2, 1))
    assert held[planes].shape == (cfg.num_layers, 3, cfg.embed_dim,
                                  cfg.embed_dim)
    assert engine._cast_only == {}
    s = engine.summary()
    assert s["weight_bytes_cast"] == 0
    assert s["weight_bytes_relaid"] == kernel.nbytes
    assert s["weight_bytes_served"] == sum(
        leaf.nbytes for leaf in jax.tree.leaves(params))
    engine.close()


# ---------------------------------------------------------------------------
# the served planes against the checkpoint's layout


def served_both_ways(build, requests, vocab, spy, monkeypatch, seed=0):
    """`requests` ((prompt length, new tokens) each) served by the engine
    `build()` makes, once as it holds a scanned stack's fused kernels
    (planes) and once with the re-lay switched off (the checkpoint's
    layout, which the modules read as they always did): asserts that
    each engine holds the layout it should, and that the tokens and
    every logit of every chunk and tick (`spy`, a LogitSpy) are bitwise
    equal between the two."""
    runs = []
    for planes in (True, False):
        with monkeypatch.context() as patch:
            if not planes:
                patch.setattr(served_weights, "_fused", lambda *_: False)
            engine = build()
            names = list(_by_path(engine._weights))
            assert any("_planes" in n for n in names) == planes
            assert any(k in n for k in served_weights.PLANES
                       for n in names) != planes
            assert (engine.summary()["weight_bytes_relaid"] > 0) == planes
            logits = spy(engine, patch).logits
            rng = np.random.default_rng(seed)
            reqs = [engine.submit(
                rng.integers(0, vocab, n).astype(np.int32),
                max_new_tokens=m) for n, m in requests]
            engine.run_until_idle()
            runs.append([(r.new_tokens, logits[r.id]) for r in reqs])
            engine.close()
    for (tokens, logits), (ref_tokens, ref_logits) in zip(*runs):
        assert tokens == ref_tokens
        assert sorted(logits) == sorted(ref_logits)
        for pos in logits:
            np.testing.assert_array_equal(logits[pos], ref_logits[pos])


def _family(family: str, dtype):
    if family == "gpt2":
        return GPT2(dataclasses.replace(_cfg(), dtype=dtype))
    quant = "int8_fwd" if family == "llama-int8fwd" else "none"
    return Llama(llama_config("test", max_seq_len=64, dtype=dtype,
                              param_dtype=jnp.float32, quant=quant))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("family", ["gpt2", "llama", "llama-int8fwd"])
def test_planes_serve_what_the_checkpoint_layout_serves(family, dtype,
                                                        monkeypatch):
    """GPT-2's fused q/k/v, and Llama's fused k/v and gate/up (under
    int8_fwd too, whose products quantize the planes by the same
    channels), served as planes: tokens and every chunk's and tick's
    logits bitwise the checkpoint layout's, in float32 and in bfloat16
    (a float32 tree then cast and re-laid in one program)."""
    from tests.test_latent_serving import LogitSpy

    model = _family(family, dtype)
    params = _init(model)
    served_both_ways(
        lambda: ServingEngine(model, params, num_slots=3, block_size=8,
                              prefill_chunk=8, prefix_cache=False),
        [(5, 6), (13, 4), (19, 8)], model.cfg.vocab_size, LogitSpy,
        monkeypatch)


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_smallthinker_planes_serve_what_the_checkpoint_layout_serves(
        compute, monkeypatch):
    """A toy SmallThinker (grouped heads: a fused k/v kernel beside its q
    kernel; its experts are `DroplessMoE`'s own leaves and stay as they
    are): tokens and logits bitwise the checkpoint layout's."""
    from benchmark import manifest, reference
    from tests.test_latent_serving import LogitSpy
    from tests.test_smallthinker_serving import TOY, make_engine

    fam = manifest.load_family(manifest.BENCH_DIR, "smallthinker")
    cfg = dict(TOY, compute_dtype=compute)
    w = jax.jit(lambda s: fam.make_weights(cfg, s))(reference.seed_u32(42))
    served_both_ways(lambda: make_engine(fam, w, cfg),
                     [(24, 6), (5, 5)], cfg["vocab_size"], LogitSpy,
                     monkeypatch)


def test_draft_swap_takes_a_float32_checkpoint():
    """`set_draft_params` compares dtypes after the cast: the float32
    tree a distillation step hands over matches the resident draft."""
    cfg = _cfg()
    model = GPT2(cfg)
    params = _init(model)
    engine = _engine("spec", model, params)
    _, dp = make_draft(GPT2(dataclasses.replace(cfg, decode=True)),
                       params, num_layers=1, spec_heads=2)
    before = _paths(engine._draft_weights, BF16)
    engine.set_draft_params(jax.tree.map(lambda x: x * 0.5, dp))
    assert engine.draft_swaps == 1
    assert _paths(engine._draft_weights, BF16) == before
    with pytest.raises(ValueError, match="dtype"):
        engine.set_draft_params(jax.tree.map(
            lambda x: x.astype(jnp.bfloat16), dp))   # bf16 norms
    engine.close()


# ---------------------------------------------------------------------------
# (e): the chaos round trip reads the one tree


def test_chaos_round_trip_on_the_served_tree():
    model = GPT2(_cfg())
    engine = ServingEngine(model, _init(model), num_slots=2,
                           prefill_bucket=16, block_size=8)
    assert engine.check_params_finite()
    good = engine._weights
    engine.set_params(nan_params(good))
    assert _paths(engine._weights, BF16) == _paths(good, BF16)
    assert not engine.check_params_finite()
    assert engine.health()["sick"]
    engine.set_params(good)
    assert engine.check_params_finite()
    assert not engine.health()["sick"]
    engine.close()


# ---------------------------------------------------------------------------
# the rule itself: read off the forward pass


def _flags(fn, tree, *operands):
    return dict(zip(sorted(tree), cast_only(fn, tree, BF16, *operands)))


def test_cast_only_follows_uses_through_bodies_and_moves():
    x = jnp.ones((4, 8), jnp.bfloat16)
    tree = {name: jnp.ones((8, 8), jnp.float32)
            for name in ("cast", "raw", "both", "rows", "unused", "f16")}
    tree["scanned"] = jnp.ones((3, 8, 8), jnp.float32)
    tree["branch"] = jnp.ones((8, 8), jnp.float32)

    def forward(w, x, which):
        y = x @ w["cast"].astype(jnp.bfloat16)
        y = y + (x.astype(jnp.float32) @ w["raw"]).astype(jnp.bfloat16)
        y = y @ w["both"].astype(jnp.bfloat16) + w["both"][0, 0]
        y = y + w["rows"][jnp.array([1, 2, 3, 4])].astype(jnp.bfloat16)
        y = y + (x @ w["f16"].astype(jnp.float16)).astype(jnp.bfloat16)
        y, _ = jax.lax.scan(
            lambda c, k: (jax.jit(lambda a, b: a @ b.astype(jnp.bfloat16))(
                c, k), None), y, w["scanned"])
        return jax.lax.cond(
            which, lambda a, k: a @ k.astype(jnp.bfloat16),
            lambda a, k: a + k.astype(jnp.bfloat16)[:4], y, w["branch"])

    assert _flags(forward, tree, x, True) == dict(
        cast=True, raw=False, both=False, rows=True, unused=False,
        f16=False, scanned=True, branch=True)
    flags = [name != "raw" for name in sorted(tree)]
    out, cast, relaid = served(tree, flags, BF16)
    assert out["raw"] is tree["raw"]
    assert all(out[k].dtype == BF16 for k in out if k != "raw")
    assert cast == sum(tree[k].nbytes for k in tree if k != "raw")
    assert relaid == 0                  # no fused kernel of a stack here
    kept, cast, relaid = served(out, flags, BF16)
    assert cast == relaid == 0 and kept is out


@pytest.mark.parametrize("family", ["llama", "moe"])
def test_leaves_read_in_float32_are_kept(family):
    """RMSNorm gains (no bias, RoPE: no position table, an untied head)
    and a router that scores in float32 stay float32; the experts and
    the head are cast."""
    if family == "llama":
        cfg = llama_config("test", max_seq_len=64, dtype=jnp.bfloat16,
                           param_dtype=jnp.float32)
        model, keep = Llama(cfg), ("ln1", "ln2", "ln_f")
    else:
        cfg = _cfg(moe_experts=4)
        model, keep = GPT2(cfg), ("ln1", "ln2", "ln_f", "router")
    params = _init(model)
    engine = ServingEngine(model, params, num_slots=2, prefill_bucket=16,
                           block_size=8)
    left = _paths(engine._weights, F32)
    assert left and all(any(f"['{k}']" in p for k in keep) for p in left)
    assert not any(f"['{k}']" in p for k in keep
                   for p in _paths(engine._weights, BF16))
    prompts = _prompts(cfg, (5, 9))
    reqs = [engine.submit(p, max_new_tokens=5) for p in prompts]
    engine.run_until_idle()
    dm = type(model)(dataclasses.replace(cfg, decode=True))
    for p, r in zip(prompts, reqs):
        ref = generate(dm, {"params": params}, jnp.asarray(p)[None],
                       max_new_tokens=5)
        np.testing.assert_array_equal(r.output_ids, np.asarray(ref)[0])
    engine.close()


def test_tensor_sharded_planes_split_what_the_kernels_split():
    """A Megatron tensor-sharded tree on a dp x tp mesh: each plane leaf
    keeps its kernel's sharding with the fused axis moved, so the tensor
    axis still splits whole columns of gate and up, and of k and v, and
    no plane is gathered onto a device."""
    import optax

    from pytorchdistributed_tpu.runtime.mesh import Axis, create_mesh
    from pytorchdistributed_tpu.training import (
        Trainer,
        token_cross_entropy_loss,
    )

    cfg = llama_config("test", max_seq_len=64)
    model = Llama(cfg)
    tr = Trainer(model, optax.sgd(1e-2), token_cross_entropy_loss,
                 mesh=create_mesh(data=2, tensor=4), strategy="tp")
    tokens = np.zeros((8, 8), np.int32)
    tr.init({"tokens": tokens, "targets": tokens})
    tree = tr.state.params["params"]
    engine = ServingEngine(model, tree, num_slots=2, block_size=8,
                           prefill_chunk=8, prefix_cache=False,
                           mesh=tr.mesh)
    held = _by_path(engine._weights)

    def split(leaf):
        """The mesh axes of more than one device that split each axis
        of `leaf`."""
        spec = tuple(leaf.sharding.spec)
        spec += (None,) * (leaf.ndim - len(spec))
        return tuple(tuple(a for a in ((e,) if isinstance(e, str) else e or ())
                           if tr.mesh.shape[a] > 1) for e in spec)

    relaid = 0
    for path, kernel in _by_path(tree).items():
        if not path.endswith(("['wi_kernel']", "['kv_kernel']")):
            continue
        want = split(kernel)
        assert want[-1] == (Axis.TENSOR,), (path, want)
        planes = held[path.replace("_kernel", "_planes")]
        assert split(planes) == want[:1] + want[2:3] + want[1:2] + want[3:]
        relaid += 1
    assert relaid == 2
    engine.close()
