"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

Drives the two main paths once, through the entry points a user calls, at
the full published width of GPT-2 small (12 L / 768 / 12 heads, vocab
50,257, bf16), random weights from a seed: the `Trainer`, and a paged
`ServingEngine` behind a `ReplicaRouter`. ONE process, which holds every
local chip; it starts no child that needs one.

It fails — non-zero exit, no result line — unless JAX reports a TPU whose
`device_kind` is in the peaks table, and it never carries on on a CPU.
Each phase passes or ends the run: nothing here turns a failed phase into
a skipped one. The only skip is the four-chip phases on a host with fewer
than four chips, and it is printed as one.

Phases: kernels (flash fwd+bwd vs dense, paged decode vs gather, compiled
never interpreted) · train on one chip · serve on one chip · train on four
chips (dp, fsdp) · serve on four chips (one in-process replica per chip).

Wall time per phase is printed split into compile and the rest, as set-up
information: it says whether the compile cache hit, and is not a
measurement of anything. The last line of stdout is the result, one JSON
object with exactly these keys:
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`;
the line before it is the summary of the phases.
"""

from __future__ import annotations

import contextlib
import importlib.metadata
import json
import sys
import time

import numpy as np

# what a Pallas kernel compiled for the chip looks like in HLO text; an
# interpreted kernel is ordinary HLO and has none
KERNEL_MARKER = "tpu_custom_call"

# bf16 kernels against their references: largest difference over the
# reference's largest magnitude. bf16 carries 8 mantissa bits (2^-8 =
# 3.9e-3 per rounding); both sides round p·V and the gradients a few
# times, so 2e-2 is a handful of roundings of head-room, and a masking or
# indexing bug is O(1).
KERNEL_TOL = 2e-2
# the engine's greedy tokens against a plain full-sequence forward of the
# same weights (teacher-forced): the reference logit of each emitted
# token may trail the reference's own best by this much. Random-init
# logits sit within ~1 of each other, so exact argmax agreement is a coin
# flip near ties; a wrong cache row or mask moves a logit by far more.
LOGIT_TOL = 5e-2
# one chip vs four chips, first-step loss: same seeded weights and batch,
# so only the order of the fp32 reductions differs
LOSS_RTOL = 1e-3

BATCH, SEQ = 8, 1024
TRAIN_STEPS, TRAIN_STEPS_4 = 5, 3
SLOTS, BLOCK, NEW_TOKENS, REQUESTS = 8, 16, 32, 8


def say(msg: str) -> None:
    print(f"[{_TAG}] {msg}", flush=True)


_TAG = "chip_smoke"
_compile = {"secs": 0.0, "hits": 0, "misses": 0}
# XLA compile, or the read that replaces it when the persistent cache hits
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _watch_compiles() -> None:
    """Sum JAX's own record of XLA compile time and of persistent-cache
    hits and misses, so a phase's wall time can be split."""
    import jax.monitoring

    def on_duration(event, secs, **_):
        if event == _COMPILE_EVENT:
            _compile["secs"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _compile["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _compile["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


@contextlib.contextmanager
def phase(name: str, report: dict):
    before, t0 = dict(_compile), time.perf_counter()
    say(f"phase {name}: start")
    yield
    wall = time.perf_counter() - t0
    comp = _compile["secs"] - before["secs"]
    report[name] = {
        "status": "passed", "setup_wall_s": round(wall, 1),
        "setup_compile_s": round(comp, 1),
        "cache_hits": _compile["hits"] - before["hits"],
        "cache_misses": _compile["misses"] - before["misses"]}
    say(f"phase {name}: passed — set-up time {wall:.1f}s wall = "
        f"{comp:.1f}s XLA compile + {wall - comp:.1f}s other; persistent "
        f"cache {report[name]['cache_hits']} hit(s), "
        f"{report[name]['cache_misses']} miss(es)")


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values")
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def compiled_text(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# kernels


def phase_kernels(cfg) -> None:
    import jax
    import jax.numpy as jnp

    from pytorchdistributed_tpu.ops.attention import (
        dense_attention,
        paged_attention,
    )
    from pytorchdistributed_tpu.ops.pallas_attention import (
        flash_attention,
        paged_flash_attention,
    )
    from pytorchdistributed_tpu.ops.quant import kv_quantize

    h, d, s = cfg.num_heads, cfg.head_dim, cfg.max_seq_len
    rng = np.random.default_rng(0)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), cfg.dtype)

    # flash forward + backward at the train step's shapes
    q, k, v, g = (normal(BATCH, s, h, d) for _ in range(4))

    def fwd_bwd(attn):
        def f(q, k, v):
            out, vjp = jax.vjp(
                lambda q, k, v: attn(q, k, v, causal=True), q, k, v)
            return (out, *vjp(g))
        return jax.jit(f)

    flash = fwd_bwd(flash_attention)
    if KERNEL_MARKER not in compiled_text(flash, q, k, v):
        raise AssertionError("flash attention compiled without a "
                             f"{KERNEL_MARKER}: interpret mode on a chip")
    for name, got, ref in zip(("out", "dq", "dk", "dv"), flash(q, k, v),
                              fwd_bwd(dense_attention)(q, k, v)):
        err = rel_err(got, ref)
        say(f"  flash {name} vs dense_attention: rel err {err:.2e} "
            f"(tol {KERNEL_TOL:.0e})")
        if err > KERNEL_TOL:
            raise AssertionError(f"flash {name} off by {err:.3e}")

    # paged decode at the serving tick's shapes: ragged live lengths,
    # block boundaries, an empty slot, a full-context slot
    pages = s // BLOCK
    nb = SLOTS * pages + 1
    lengths = np.array([0, 1, BLOCK - 1, BLOCK, 5 * BLOCK + 3, s // 2,
                        s - BLOCK, s - 1][:SLOTS], np.int32)
    tables = np.zeros((SLOTS, pages), np.int32)
    for slot, n in enumerate(lengths):  # blocks past the live length: trash
        live = n // BLOCK + 1
        tables[slot, :live] = 1 + slot * pages + np.arange(live)
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    for label, hk, int8 in (("bf16", h, False), ("int8", h, True),
                            (f"bf16 GQA {h}q/{h // 3}kv", h // 3, False)):
        qd = normal(SLOTS, h, d)
        kp, vp = normal(nb, BLOCK, hk, d), normal(nb, BLOCK, hk, d)
        scales = {}
        if int8:
            (kp, ks), (vp, vs) = kv_quantize(kp), kv_quantize(vp)
            scales = dict(k_scale=ks, v_scale=vs)
        # the pool's row: one token's kv heads side by side
        kp, vp = kp.reshape(nb, BLOCK, hk * d), vp.reshape(nb, BLOCK, hk * d)
        kernel = jax.jit(lambda q, kp, vp, sc: paged_flash_attention(
            q, kp, vp, tables, lengths, **sc))
        if KERNEL_MARKER not in compiled_text(kernel, qd, kp, vp, scales):
            raise AssertionError(f"paged decode ({label}) compiled without "
                                 f"a {KERNEL_MARKER}")
        ref = jax.jit(lambda q, kp, vp, sc: paged_attention(
            q[:, None], kp, vp, tables, lengths, **sc)[:, 0])
        err = rel_err(kernel(qd, kp, vp, scales), ref(qd, kp, vp, scales))
        say(f"  paged decode {label} vs gather: rel err {err:.2e} "
            f"(tol {KERNEL_TOL:.0e})")
        if err > KERNEL_TOL:
            raise AssertionError(f"paged decode ({label}) off by {err:.3e}")


# ---------------------------------------------------------------------------
# training


def seeded_batch(cfg) -> dict:
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (BATCH, SEQ + 1)).astype(
        np.int32)
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def train(cfg, mesh, strategy: str, steps: int):
    """``steps`` optimizer steps of the one seeded batch; returns the
    losses and the trainer (for its shardings)."""
    import optax

    from pytorchdistributed_tpu.models import GPT2
    from pytorchdistributed_tpu.training import (
        Trainer,
        token_cross_entropy_loss,
    )

    trainer = Trainer(GPT2(cfg), optax.adamw(1e-3), token_cross_entropy_loss,
                      mesh=mesh, strategy=strategy, log_every=10**9)
    batch = seeded_batch(cfg)
    trainer.init(batch)
    losses = [float(trainer.train_step(batch)["loss"]) for _ in range(steps)]
    say(f"  {strategy} on {mesh.devices.size} chip(s): losses "
        + " ".join(f"{x:.4f}" for x in losses))
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    hlo = trainer.lower_step(batch).compile().as_text()
    if KERNEL_MARKER not in hlo:
        raise AssertionError(f"train step compiled without a "
                             f"{KERNEL_MARKER}: interpret mode on a chip")
    say(f"  compiled step holds {hlo.count(KERNEL_MARKER)} {KERNEL_MARKER}")
    return losses, trainer


def phase_train_one(cfg) -> float:
    import jax

    from pytorchdistributed_tpu.runtime.mesh import create_mesh

    losses, _ = train(cfg, create_mesh(devices=jax.devices()[:1]), "dp",
                      TRAIN_STEPS)
    return losses[0]


def phase_train_four(cfg, one_chip_first_loss: float) -> None:
    import jax

    from pytorchdistributed_tpu.runtime.mesh import create_mesh

    devices = jax.devices()[:4]
    for strategy, axes in (("dp", dict(data=4)),
                           ("fsdp", dict(data=1, fsdp=4))):
        losses, trainer = train(cfg, create_mesh(devices=devices, **axes),
                                strategy, TRAIN_STEPS_4)
        if not np.isclose(losses[0], one_chip_first_loss, rtol=LOSS_RTOL):
            raise AssertionError(
                f"{strategy}: first loss {losses[0]} vs one chip "
                f"{one_chip_first_loss} (rtol {LOSS_RTOL})")
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                trainer.state.params):
            if len(leaf.sharding.device_set) != 4:
                raise AssertionError(
                    f"{strategy}: {jax.tree_util.keystr(path)} lives on "
                    f"{len(leaf.sharding.device_set)} device(s)")
        in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
        say(f"  {strategy}: parameters span 4 devices; bytes in use per "
            f"device {in_use}")
        if min(in_use) <= 0:
            raise AssertionError(f"{strategy}: an idle device: {in_use}")
        del trainer  # frees the state before the next strategy's


# ---------------------------------------------------------------------------
# serving


def seeded_prompts(cfg) -> list[np.ndarray]:
    rng = np.random.default_rng(1)
    return [rng.integers(0, cfg.vocab_size, (int(n),)).astype(np.int32)
            for n in rng.integers(16, 97, REQUESTS)]


def serve(model, params, router, prompts) -> list:
    """warmup, then the seeded requests, greedy; every check that does
    not depend on how many replicas there are."""
    import jax
    import jax.numpy as jnp

    from pytorchdistributed_tpu.serving.engine import TRACE_COUNTS

    cfg = model.cfg
    router.warmup()
    traces = dict(TRACE_COUNTS)
    reqs = []
    for p in prompts:
        reqs.append(router.submit(p, max_new_tokens=NEW_TOKENS))
        router.step()  # staggered arrivals interleave with decoding
    router.run_until_idle()
    if dict(TRACE_COUNTS) != traces:
        raise AssertionError(f"fresh traces after warmup: {traces} -> "
                             f"{dict(TRACE_COUNTS)}")
    forward = jax.jit(lambda p, t: model.apply(p, t))
    worst = 0.0
    for r in reqs:
        toks = np.asarray(r.tokens)
        if not (r.done and r.finish_reason == "length"
                and len(toks) == NEW_TOKENS):
            raise AssertionError(
                f"request {r.id}: done={r.done} reason={r.finish_reason} "
                f"tokens={len(toks)}")
        if toks.min() < 0 or toks.max() >= cfg.vocab_size:
            raise AssertionError(f"request {r.id}: token out of vocabulary")
        # teacher-forced reference: one plain forward over prompt +
        # emitted tokens (padded to one shape) scores every position the
        # engine decoded — prefill chunk, cache writes and decode kernel
        seq = np.zeros(128, np.int32)
        seq[:len(r.output_ids)] = r.output_ids
        logits = np.asarray(forward(params, jnp.asarray(seq)[None])[0])
        first = len(r.prompt) - 1
        rows = logits[first:first + NEW_TOKENS]
        if not np.isfinite(rows).all():
            raise AssertionError(f"request {r.id}: non-finite logits")
        regret = rows.max(-1) - rows[np.arange(NEW_TOKENS), toks]
        worst = max(worst, float(regret.max()))
    say(f"  {len(reqs)} requests finished ({NEW_TOKENS} tokens each, "
        f"prompts {min(map(len, prompts))}-{max(map(len, prompts))}); zero "
        f"fresh traces after warmup; emitted tokens trail the plain "
        f"forward's best logit by at most {worst:.2e} (tol {LOGIT_TOL:.0e})")
    if worst > LOGIT_TOL:
        raise AssertionError(f"engine tokens trail the reference forward "
                             f"by {worst:.3e}")
    return reqs


def phase_serve_one(model, params) -> str:
    import dataclasses

    import jax.numpy as jnp

    from pytorchdistributed_tpu import generate
    from pytorchdistributed_tpu.serving import ReplicaRouter, ServingEngine

    engines = []

    def factory():
        engines.append(ServingEngine(model, params, num_slots=SLOTS,
                                     block_size=BLOCK))
        return engines[-1]

    router = ReplicaRouter(factories=[factory])
    prompts = seeded_prompts(model.cfg)
    reqs = serve(model, params, router, prompts)
    engine = engines[0]
    mode = engine.summary()["paged_attn"]
    say(f"  engine paged_attn={mode} native_gather={_native()}")
    has_kernel = KERNEL_MARKER in engine.lower_tick().compile().as_text()
    if has_kernel != (mode == "pallas"):
        raise AssertionError(
            f"paged_attn={mode} but the decode tick "
            f"{'holds' if has_kernel else 'has no'} {KERNEL_MARKER}")
    # information only: random-init argmax is too brittle to gate on
    decode_model = model.clone(
        cfg=dataclasses.replace(model.cfg, decode=True))
    same = total = 0
    for r, p in list(zip(reqs, prompts))[:2]:
        ref = np.asarray(generate(decode_model, params, jnp.asarray(p)[None],
                                  max_new_tokens=NEW_TOKENS))[0, len(p):]
        same += int((ref == np.asarray(r.tokens)).sum())
        total += NEW_TOKENS
    say(f"  info: {same}/{total} tokens equal generate()'s on the first "
        f"two requests")
    router.close()
    return mode


def phase_serve_four(model, params) -> list[dict]:
    import jax

    from pytorchdistributed_tpu.serving import ReplicaRouter, ServingEngine

    engines = {}

    def make_factory(i, dev):
        def factory():
            # weights committed to chip i pull the engine's programs
            # there; default_device lands its fresh KV pool there too
            with jax.default_device(dev):
                engines[i] = ServingEngine(
                    model, jax.device_put(params, dev), num_slots=SLOTS,
                    block_size=BLOCK)
            return engines[i]
        return factory

    router = ReplicaRouter(factories=[
        make_factory(i, d) for i, d in enumerate(jax.devices()[:4])])
    reqs = serve(model, params, router, seeded_prompts(model.cfg))
    placed = []
    for i in range(4):
        where = engines[i].placement()
        served = sum(1 for r in reqs if i in r.replicas)
        placed.append({"replica": i, **where, "requests": served})
        say(f"  replica {i}: weights on device(s) {where['weights']}, KV "
            f"pool on {where['kv']}, served {served} request(s)")
        if where["weights"] != [jax.devices()[i].id] or \
                where["kv"] != where["weights"]:
            raise AssertionError(f"replica {i} is not on its chip: {where}")
    router.close()
    return placed


def _native() -> bool:
    from pytorchdistributed_tpu._native import native_available

    return native_available()


# ---------------------------------------------------------------------------


def main() -> int:
    global _TAG
    import flax.linen as nn
    import jax
    import jaxlib

    from pytorchdistributed_tpu.models import GPT2, gpt2_config
    from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache
    from pytorchdistributed_tpu.telemetry import PEAK_BF16_FLOPS

    cache_dir = use_persistent_cache()
    dev, count = jax.devices()[0], len(jax.devices())
    _TAG = f"platform={dev.platform} device_kind={dev.device_kind!r} x{count}"
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": importlib.metadata.version("libtpu")}
    say(f"versions {versions}; compile cache {cache_dir}")
    if dev.platform != "tpu" or dev.device_kind not in PEAK_BF16_FLOPS:
        print(f"chip_smoke: needs a TPU whose device_kind is in the peaks "
              f"table (telemetry/accounting.py); JAX found "
              f"platform={dev.platform} device_kind={dev.device_kind!r}",
              file=sys.stderr)
        return 1
    _watch_compiles()

    report: dict = {}
    cfg = gpt2_config("small", attention="pallas")
    with phase("kernels", report):
        phase_kernels(cfg)
    with phase("train_one_chip", report):
        first_loss = phase_train_one(cfg)
    model = GPT2(gpt2_config("small"))
    params = nn.meta.unbox(jax.jit(model.init)(
        jax.random.key(0), np.zeros((1, 8), np.int32)))
    with phase("serve_one_chip", report):
        paged_attn = phase_serve_one(model, params)
    replicas = None
    if count >= 4:
        with phase("train_four_chips", report):
            phase_train_four(cfg, first_loss)
        with phase("serve_four_chips", report):
            replicas = phase_serve_four(model, params)
    else:
        for name in ("train_four_chips", "serve_four_chips"):
            report[name] = {"status": f"skipped: {count} device(s)"}
            say(f"phase {name}: skipped: {count} device(s)")

    say("summary " + json.dumps({
        "versions": versions, "compile_cache_dir": cache_dir,
        "native_gather": _native(), "paged_attn": paged_attn,
        "first_loss_one_chip": first_loss, "replicas": replicas,
        "phases": report, "claim": None}))
    print(result_line(dev, count), flush=True)
    return 0


def result_line(dev, count: int) -> str:
    """The last line of stdout: these keys and no others, the device as
    JAX reports it. Printed only when every phase passed."""
    return json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": count}})


if __name__ == "__main__":
    sys.exit(main())
