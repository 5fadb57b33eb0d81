"""Subprocess serving replica — the worker half of ReplicaRouter's
multi-process mode (ISSUE 9).

    PTD_REPLICA_SPEC='{"model": "gpt2", "size": "test", ...}' \
    RANK=0 WORLD_SIZE=2 python -m pytorchdistributed_tpu.serving.replica_worker

Reads the same env contract run.py gives training workers (RANK is the
replica index; MASTER_* ride along for future cross-replica state) plus
a JSON ``PTD_REPLICA_SPEC`` describing the model/engine to build, then
serves a line-JSON protocol on stdin/stdout — one response per op:

    {"op": "warmup", "prompt_lens": [16]}        -> {"ok": true}
    {"op": "submit", "rid": 3, "prompt": [...], ...} -> {"ok": true}
    {"op": "step"}   -> {"ok": true, "delivered": [[rid, tok], ...],
                         "finished": [[rid, reason], ...],
                         "health": {...}}
    {"op": "probe"}  -> {"finite": true}
    {"op": "drain"}  -> {"ok": true, "finished": [...]}
    {"op": "close"}  -> {"ok": true}  (then exits 0)

Liveness: PTD_HEARTBEAT_DIR (the run.py contract) gets a beat after
every step op — each beat follows the engine's host sync of device
results, honoring runtime/heartbeat.py's device-sync rule. SIGTERM
drains the engine and exits 0 (the router forwards it on teardown;
kill_group escalation covers a wedged worker). PTD_FAULTS serving
faults fire HERE, against this worker's own RANK: ``replica_crash``
os._exits mid-protocol, ``replica_hang`` SIGSTOPs (alive, silent — the
router's watchdog must catch it), ``replica_nan`` NaNs the params so
the router's probe op must come back non-finite.

The spec: {"model": "gpt2"|"llama", "size": "test", "overrides": {...
TransformerConfig overrides}, "init_seed": 1, "engine": {...
ServingEngine kwargs}, "max_seq_len": ..., "checkpoint": <dir>,
"checkpoint_step": <int>}. Params come from
``"checkpoint"`` when set — training/checkpoint.py's VERIFIED
params-only restore (manifest-checked, corrupt steps quarantined and
walked past), falling back to ``init_seed`` with a logged
TelemetryEvent when the checkpoint is absent or unusable (a worker
that cannot load weights must still join the fleet deterministically,
not die in a respawn loop). Compiled programs come from JAX's
persistent compilation cache (runtime/xla_cache.py), which a respawned
worker shares with the one it replaces.

Speculative drafts (ISSUE 16): ``spec["engine"]["draft"]`` = {"num_layers":
<int|null>, "spec_heads": <int>, "checkpoint": <dir>, "checkpoint_step":
<int>} builds the draft with ``inference.make_draft`` (truncating the
TARGET's own restored weights, attaching zero-init proposal heads) and,
when the draft checkpoint is present, hot-loads the distilled weights
through the engine's verified ``set_draft_params`` path. The
``set_draft_params`` wire op carries a CHECKPOINT PATH, never a weight
tree: the worker restores it locally (CheckpointManager.restore_params —
the same manifest-verified restore as boot) and the engine's
structure/shape check decides; streams in flight keep their K/V and
their token-for-token identity (the spec rejection kernel is lossless
under ANY draft).
"""

from __future__ import annotations

import json
import os
import signal
import sys


def _load_params(spec: dict, model):
    """The worker's weights: a verified checkpoint restore when the
    spec names one (TelemetryEvent either way), else deterministic
    seed-init — replicas agree on params without shipping weights over
    a pipe."""
    import jax
    import jax.numpy as jnp

    from pytorchdistributed_tpu.telemetry.events import (
        EVENT_REPLICA_RESTORE,
        EVENT_REPLICA_RESTORE_FALLBACK,
        EventLog,
    )

    events = EventLog.from_env(int(os.environ.get("RANK", "0")))
    ckpt = spec.get("checkpoint")
    if ckpt:
        try:
            from pytorchdistributed_tpu.training.checkpoint import (
                CheckpointManager,
            )

            mgr = CheckpointManager(ckpt)
            try:
                params, step = mgr.restore_params(
                    step=spec.get("checkpoint_step"))
            finally:
                mgr.close()
            # Restored-as-saved trees carry orbax's rendering of flax
            # metadata nodes (nn.Partitioned boxes become plain dicts),
            # so re-shape the leaves onto the MODEL's own abstract
            # params structure — leaf order is stable (both are DFS
            # over the same module-path dicts; a metadata box is a
            # singleton wrapper) and the shape check below turns any
            # genuine mismatch (wrong model for this checkpoint) into
            # the seed-init fallback instead of a garbled apply. Also
            # re-commits host-numpy leaves to device arrays once.
            import flax.linen as nn

            abstract = nn.meta.unbox(jax.eval_shape(
                lambda: model.init(jax.random.key(0),
                                   jnp.zeros((1, 8), jnp.int32))))
            treedef = jax.tree_util.tree_structure(abstract)
            leaves = jax.tree_util.tree_leaves(params)
            want = jax.tree_util.tree_leaves(abstract)
            if len(leaves) != len(want):
                raise ValueError(
                    f"checkpoint has {len(leaves)} param leaves, model "
                    f"expects {len(want)}")
            for have, sds in zip(leaves, want):
                if tuple(have.shape) != tuple(sds.shape):
                    raise ValueError(
                        f"checkpoint leaf shape {tuple(have.shape)} != "
                        f"model's {tuple(sds.shape)}")
            if events is not None:
                events.emit(EVENT_REPLICA_RESTORE, step=step,
                            checkpoint=str(ckpt))
            return jax.tree_util.tree_unflatten(
                treedef, [jnp.asarray(x) for x in leaves])
        except Exception as e:  # noqa: BLE001 — worker must still join
            if events is not None:
                events.emit(EVENT_REPLICA_RESTORE_FALLBACK, step=-1,
                            checkpoint=str(ckpt),
                            error=f"{type(e).__name__}: {e}"[:200])
    return jax.jit(model.init)(
        jax.random.key(int(spec.get("init_seed", 0))),
        jnp.zeros((1, 8), jnp.int32))


def _restore_draft_params(path, step=None):
    """Verified params-only restore for a DRAFT weight tree (boot-time
    ``draft.checkpoint`` and the ``set_draft_params`` wire op share it).
    Raises on a missing/corrupt checkpoint — the engine-side structure
    and shape check then decides whether the tree actually fits."""
    import jax.numpy as jnp
    import jax

    from pytorchdistributed_tpu.training.checkpoint import (
        CheckpointManager,
    )

    mgr = CheckpointManager(path)
    try:
        params, ckpt_step = mgr.restore_params(step=step)
    finally:
        mgr.close()
    # re-commit host-numpy leaves once, as _load_params does
    return jax.tree.map(jnp.asarray, params), ckpt_step


def _drain_demoted_sessions(engine) -> list:
    """Wire-encode whatever sessions the engine's HBM budget (or its
    drain) demoted since the last sweep — they ride step/drain replies
    to the router, which persists them into the store tiers."""
    demoted = engine.take_demoted_sessions()
    if not demoted:
        return []
    from pytorchdistributed_tpu.serving.engine import kv_payload_to_wire

    return [[sid, tenant, kv_payload_to_wire(payload)]
            for sid, tenant, payload in demoted]


def _build_engine(spec: dict):
    from pytorchdistributed_tpu.models import (
        GPT2,
        Llama,
        gpt2_config,
        llama_config,
    )
    from pytorchdistributed_tpu.serving.engine import ServingEngine
    from pytorchdistributed_tpu.serving.telemetry import ServingTelemetry

    kind = spec.get("model", "gpt2")
    size = spec.get("size", "test")
    overrides = dict(spec.get("overrides", {}))
    if kind == "llama":
        cfg = llama_config(size, **overrides)
        model = Llama(cfg)
    else:
        cfg = gpt2_config(size, **overrides)
        model = GPT2(cfg)
    params = _load_params(spec, model)
    telemetry = ServingTelemetry.from_env()
    # each worker writes its own trace_rank{RANK}.jsonl — None (and
    # zero per-request work) unless the launcher exported PTD_TRACE
    from pytorchdistributed_tpu.telemetry.tracing import RequestTracer

    trace = RequestTracer.from_env()
    engine_kwargs = dict(spec.get("engine", {}))
    draft = engine_kwargs.pop("draft", None)
    draft_ckpt = None
    if draft:
        from pytorchdistributed_tpu.inference import make_draft

        draft_model, draft_params = make_draft(
            model, params, num_layers=draft.get("num_layers"),
            spec_heads=int(draft.get("spec_heads", 0)),
            seed=int(draft.get("seed", 0)))
        engine_kwargs.setdefault("draft_config", draft_model.cfg)
        engine_kwargs.setdefault("draft_params", draft_params)
        draft_ckpt = draft.get("checkpoint")
    engine = ServingEngine(model, params, telemetry=telemetry,
                           trace=trace, **engine_kwargs)
    if draft_ckpt:
        # distilled weights ride the SAME verified path as a later
        # hot-swap — a bad draft checkpoint degrades to the warm-start
        # draft (still lossless), it never kills the worker
        try:
            restored, _ = _restore_draft_params(
                draft_ckpt, draft.get("checkpoint_step"))
            engine.set_draft_params(restored)
        except Exception as e:  # noqa: BLE001 — worker must still join
            print(f"draft checkpoint {draft_ckpt} unusable "
                  f"({type(e).__name__}: {e}); serving warm-start draft",
                  file=sys.stderr)
    return engine


def main() -> int:
    spec = json.loads(os.environ.get("PTD_REPLICA_SPEC", "{}"))
    rank = int(os.environ.get("RANK", "0"))

    from pytorchdistributed_tpu.faults.inject import FaultInjector
    from pytorchdistributed_tpu.runtime.heartbeat import Heartbeat
    from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache

    use_persistent_cache()
    engine = _build_engine(spec)
    heartbeat = Heartbeat.from_env()
    injector = FaultInjector.from_env()
    delivered: list[list[int]] = []
    finished: list[list] = []
    reqs: dict[int, object] = {}

    def on_token(req, tok):
        delivered.append([req.router_rid, int(tok)])

    def sweep_finished() -> None:
        for rid, req in list(reqs.items()):
            if req.done:
                finished.append([rid, req.finish_reason])
                del reqs[rid]

    def reply(**payload) -> None:
        sys.stdout.write(json.dumps(payload) + "\n")
        sys.stdout.flush()

    # SIGTERM must work while BLOCKED in the stdin read (the idle
    # worker's steady state — PEP 475 would otherwise retry the read
    # after a flag-setting handler and the drain would wait for the
    # next op that never comes): raise out of the read and let the
    # finally-drain run. Raising between ops is safe — the engine is
    # only ever mutated inside a fully-completed op handler.
    def _sigterm(signum, frame):
        raise SystemExit(0)

    signal.signal(signal.SIGTERM, _sigterm)
    closed = [False]

    def shutdown() -> None:
        if not closed[0]:
            closed[0] = True
            engine.drain()
            engine.close()
            if engine.trace is not None:
                engine.trace.close()

    try:
        return _serve(engine, heartbeat, injector, rank, delivered,
                      finished, reqs, on_token, sweep_finished, reply,
                      shutdown)
    finally:
        # every exit path — close op, stdin EOF, SIGTERM — drains the
        # engine (pool-leak invariant asserted) exactly once
        shutdown()


def _serve(engine, heartbeat, injector, rank, delivered, finished, reqs,
           on_token, sweep_finished, reply, shutdown) -> int:
    tick = 0
    slow_ms = 0.0   # injected straggler latency, paid on the next step
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        op = json.loads(line)
        kind = op.get("op")
        if kind == "warmup":
            engine.warmup(prompt_lens=op.get("prompt_lens") or None)
            if op.get("kv_stream"):
                # compile the KV gather/scatter pair now so the first
                # real handoff/prefix ship is dispatch-only
                engine.warmup_kv_stream()
            # report the real context bound so the router can validate
            # submits against it instead of trusting the spec — and a
            # first health snapshot, so role decisions (block_size,
            # free_slots) don't wait for the first step reply
            reply(ok=True, max_seq_len=engine.cfg.max_seq_len,
                  health=engine.health())
        elif kind == "submit":
            s = op.get("sampling", {})
            from pytorchdistributed_tpu.serving.engine import (
                SamplingParams,
            )
            try:
                req = engine.submit(
                    op["prompt"], max_new_tokens=op["max_new_tokens"],
                    sampling=SamplingParams(
                        temperature=float(s.get("temperature", 0.0)),
                        top_k=int(s.get("top_k", 0)),
                        top_p=float(s.get("top_p", 1.0)),
                        seed=int(s.get("seed", 0))),
                    stop_ids=tuple(op.get("stop_ids") or ()),
                    deadline_s=op.get("deadline_s"),
                    generated=op.get("generated") or None,
                    on_token=on_token,
                    prefill_only=bool(op.get("prefill_only")),
                    kv_window=op.get("kv_window"),
                    kv_sink=op.get("kv_sink"),
                    session_id=op.get("session_id"),
                    tenant=op.get("tenant", "default"),
                    trace=op.get("trace"),
                    origin_t=op.get("origin_t"))
            except ValueError as e:
                # a malformed request must cost ONE refusal, not the
                # worker process (and then, replica by replica, the
                # fleet as the router redispatches it)
                reply(ok=False, rid=op["rid"], error=str(e))
                continue
            req.router_rid = op["rid"]
            reqs[op["rid"]] = req
            reply(ok=True, rid=op["rid"])
        elif kind == "step":
            tick += 1
            if injector is not None:
                fault = injector.on_serving_tick(tick, rank)
                if fault == "replica_crash":
                    from pytorchdistributed_tpu.faults.inject import (
                        CRASH_EXIT_CODE,
                    )

                    sys.stdout.flush()
                    os._exit(CRASH_EXIT_CODE)
                elif fault == "replica_hang":
                    os.kill(os.getpid(), signal.SIGSTOP)
                elif fault == "replica_nan":
                    from pytorchdistributed_tpu.serving.engine import (
                        nan_params,
                    )

                    engine.set_params(nan_params(engine._weights))
                elif fault == "replica_slow":
                    spec = getattr(injector, "last_fired", None)
                    slow_ms += spec.ms if spec is not None else 100.0
            if slow_ms > 0:
                # a straggler, not a hang: the step still completes and
                # the progress watermark advances — just late
                import time as _time

                _time.sleep(slow_ms / 1e3)
                slow_ms = 0.0
            engine.step()
            sweep_finished()
            if heartbeat is not None:
                heartbeat.beat()  # after the engine's host sync
            step_reply = dict(
                ok=True, delivered=list(delivered),
                finished=list(finished), health=engine.health(),
                parked=[r.router_rid for r in engine.parked_requests
                        if hasattr(r, "router_rid")])
            demoted = _drain_demoted_sessions(engine)
            if demoted:
                step_reply["demoted_sessions"] = demoted
            reply(**step_reply)
            # clear IN PLACE: on_token/sweep_finished close over these
            delivered.clear()
            finished.clear()
        elif kind == "export_kv":
            from pytorchdistributed_tpu.serving.engine import (
                kv_payload_to_wire,
            )

            req = reqs.get(op["rid"])
            if req is None:
                reply(ok=False, error=f"unknown rid {op['rid']}")
                continue
            try:
                payload = engine.export_kv_blocks(req)
            except ValueError as e:
                reply(ok=False, error=str(e))
                continue
            del reqs[op["rid"]]  # the stream now lives in the payload
            reply(ok=True, rid=op["rid"],
                  payload=kv_payload_to_wire(payload))
        elif kind == "import_kv":
            from pytorchdistributed_tpu.serving.engine import (
                kv_payload_from_wire,
            )

            try:
                req = engine.import_kv_blocks(
                    kv_payload_from_wire(op["payload"]),
                    on_token=on_token, deadline_s=op.get("deadline_s"))
            except ValueError as e:
                reply(ok=False, error=str(e))
                continue
            if req is None:   # pool pressure: refuse, router requeues
                reply(ok=False, error="no free slot/blocks")
                continue
            req.router_rid = op["rid"]
            reqs[op["rid"]] = req
            reply(ok=True, rid=op["rid"])
        elif kind == "export_prefix":
            import numpy as np

            from pytorchdistributed_tpu.serving.engine import (
                prefix_payload_to_wire,
            )

            payload = engine.export_prefix_blocks(
                np.asarray(op["tokens"], np.int32))
            if payload is None:
                reply(ok=False)
            else:
                reply(ok=True, payload=prefix_payload_to_wire(payload))
        elif kind == "import_prefix":
            from pytorchdistributed_tpu.serving.engine import (
                prefix_payload_from_wire,
            )

            adopted = engine.import_prefix_blocks(
                prefix_payload_from_wire(op["payload"]))
            reply(ok=True, adopted=int(adopted))
        elif kind == "preempt":
            # admission-side preemption (ISSUE 15): evict the stream
            # losslessly — its tokens flow back as a "preempted" finish
            # through the next step reply and the router requeues it
            req = reqs.get(op["rid"])
            ok = req is not None and engine.preempt_request(req)
            if ok:
                finished.append([op["rid"], "preempted"])
                del reqs[op["rid"]]
            reply(ok=bool(ok), rid=op["rid"])
        elif kind == "set_draft_params":
            # fleet draft hot-swap (ISSUE 16): checkpoint-path payload,
            # restored locally and verified by the engine's structure/
            # shape check; in-flight spec streams keep their K/V and
            # stay token-for-token identical (lossless under any draft)
            try:
                params, step = _restore_draft_params(
                    op["checkpoint"], op.get("step"))
                engine.set_draft_params(params)
            except Exception as e:  # noqa: BLE001 — refusal, not death
                reply(ok=False, error=f"{type(e).__name__}: {e}"[:300])
                continue
            reply(ok=True, step=step,
                  draft_hash=engine.draft_params_hash(),
                  draft_swaps=engine.draft_swaps)
        elif kind == "probe":
            reply(finite=engine.check_params_finite())
        elif kind == "inject":
            # router-side rate-based chaos (ISSUE 19): the ChaosSchedule
            # lives in the ROUTER process (one seed, one decision
            # stream), so nan/slow verdicts arrive as a wire op the
            # worker applies to its own engine. crash/hang never ride
            # this path — the router kills/SIGSTOPs the process itself.
            what = op.get("kind")
            if what == "replica_nan":
                from pytorchdistributed_tpu.serving.engine import (
                    nan_params,
                )

                engine.set_params(nan_params(engine._weights))
            elif what == "replica_slow":
                slow_ms += float(op.get("ms", 100.0))
            reply(ok=True, kind=what)
        elif kind == "export_session":
            # persistent sessions (ISSUE 18): hand a RESIDENT parked
            # session's KV over the wire (cross-replica reattach pull)
            from pytorchdistributed_tpu.serving.engine import (
                kv_payload_to_wire,
            )

            payload = engine.export_session(op["session_id"])
            if payload is None:
                reply(ok=False, error="no such resident session")
            else:
                reply(ok=True, payload=kv_payload_to_wire(payload))
        elif kind == "seed_session":
            from pytorchdistributed_tpu.serving.engine import (
                kv_payload_from_wire,
            )

            seeded = engine.seed_session_blocks(
                kv_payload_from_wire(op["payload"]), remote=True)
            reply(ok=True, seeded=int(seeded))
        elif kind == "drain":
            engine.drain()
            sweep_finished()
            drain_reply = dict(ok=True, finished=list(finished))
            demoted = _drain_demoted_sessions(engine)
            if demoted:
                # the drain demoted every resident session — the router
                # persists them (clean drain) or discards (quarantine)
                drain_reply["demoted_sessions"] = demoted
            reply(**drain_reply)
            finished.clear()
        elif kind == "close":
            shutdown()  # drain + close exactly once (finally is a noop)
            sweep_finished()
            reply(ok=True, finished=finished)
            return 0
        else:
            reply(ok=False, error=f"unknown op {kind!r}")
    # stdin EOF: the router died — the caller's finally drains and
    # closes, so the worker never lingers as an orphan
    return 0


if __name__ == "__main__":
    sys.exit(main())
