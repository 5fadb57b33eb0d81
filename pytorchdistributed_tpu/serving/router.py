"""Replicated serving: a health-checked replica router with lossless
mid-stream failover (ISSUE 9 — ROADMAP item 3's traffic-scale half).

Everything below serving/engine.py serves from ONE engine on ONE mesh: a
single crash, hang, or NaN'd parameter tree kills every in-flight stream
and drops the queue. The reference tutorial's whole fault-tolerance story
is the torchrun elastic agent — detect a dead worker, relaunch the job
from the env-contract rendezvous (SURVEY §2b; reproduced for *training*
in PR 4). This module is the SERVING restatement of that contract:

  * a host-side ``ReplicaRouter`` owns N ``ServingEngine`` replicas —
    in-process (the CPU test tier and single-host multi-engine) or as
    SUBPROCESS workers launched with the same RANK/WORLD_SIZE/MASTER_*
    env contract ``run.py`` gives training workers, SIGTERM forwarding
    and ``kill_group`` escalation included;
  * ``submit()`` load-balances across replicas on the telemetry the
    engine already emits (slot occupancy, queue depth, pool pressure,
    TTFT EMA — ``ServingEngine.health()``);
  * every replica is health-checked per router tick: a **progress
    watermark** (monotonic completed-compiled-call counter, the serving
    analog of runtime/heartbeat.py's device-sync'd beats) catches hangs
    within a bounded number of ticks, process exit / pipe EOF catches
    crashes immediately, and a periodic compiled **params-finite probe**
    catches a NaN'd replica (the diagnostics-tripwire analog: garbage
    *tokens* are perfectly finite ints, the *params* are where the rot
    is visible);
  * the robustness core is **lossless mid-stream failover**: every
    request the router hands out carries its prompt, sampling params,
    seed and generated-so-far tokens, so when a replica dies its
    in-flight requests are redispatched to a survivor, which resumes by
    re-prefilling prompt+generated (``submit(generated=...)`` — the
    exact preempt-requeue mechanism the paged engine already proved
    bitwise-safe). The client-visible greedy stream is **bitwise
    identical** to an uninterrupted single-engine run, and seeded
    sampled streams continue their fold_in sequence exactly where the
    dead replica left them;
  * on top: a per-request retry budget with ``faults/retry.py`` backoff
    between redispatches, admission-control **load shedding** (bounded
    router queue → immediate ``finish_reason="shed"`` instead of
    unbounded latency), replica **quarantine/rejoin** with a warmup
    canary re-admission, and router-level graceful **drain on SIGTERM**
    (finish resident streams, shed the queue, leave no orphan replica);
  * and since ISSUE 10, **auto-respawn**: a DEAD replica is RELAUNCHED
    (``respawn_budget`` attempts with exponential backoff) — subprocess
    workers restart under the same env/spec contract, restoring weights
    from a verified checkpoint and their executables from JAX's
    persistent compilation cache (runtime/xla_cache.py) — and
    rejoins through the same quarantine → clean-probe → canary gauntlet
    as a NaN recovery. A crash is a transient, not a permanent capacity
    loss; torchrun's elastic agent, restated for serving.

Chaos is first-class: ``faults/inject.py`` grew ``replica_crash`` /
``replica_hang`` / ``replica_nan`` serving faults (``PTD_FAULTS`` /
``run.py --faults`` syntax, targeted by replica index and router tick);
the router consults the process-global injector every tick and applies
whatever fires. tests/test_router.py is the chaos suite.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import random
import select
import subprocess
import sys
import time

import numpy as np

from pytorchdistributed_tpu.faults import inject as faults_inject
from pytorchdistributed_tpu.faults.retry import RetryPolicy
from pytorchdistributed_tpu.serving.engine import (
    SamplingParams,
    ServingEngine,
    kv_payload_from_wire,
    kv_payload_to_wire,
    prefix_payload_from_wire,
    prefix_payload_to_wire,
)
from pytorchdistributed_tpu.serving.paging import (
    FleetPrefixIndex,
    FleetSessionIndex,
    block_hashes,
)
from pytorchdistributed_tpu.serving.telemetry import RouterTelemetry
from pytorchdistributed_tpu.telemetry.events import TELEMETRY_DIR_ENV
from pytorchdistributed_tpu.telemetry.spans import span
from pytorchdistributed_tpu.telemetry.tracing import (
    RequestTracer,
    to_unix as _trace_to_unix,
)

#: Replica lifecycle states. HEALTHY serves traffic; QUARANTINED is
#: alive but sick (params non-finite) — probed every tick, rejoined
#: after a clean streak + canary; DEAD is crashed or hung (its requests
#: were failed over) and never returns. ISSUE 15 adds the scale-down
#: pair: DRAINING still steps (resident streams finish, parked prefills
#: hand off) but admits nothing new, and REMOVED is a tombstone — the
#: parallel per-replica lists are never renumbered, so a removed
#: replica's counters and occupancy history survive into the summary.
HEALTHY, QUARANTINED, DEAD = "healthy", "quarantined", "dead"
DRAINING, REMOVED = "draining", "removed"

#: Replica roles (ISSUE 12 — prefill/decode disaggregation). A
#: ``prefill``-role replica runs chunked prefill only: its requests are
#: submitted ``prefill_only`` and PARK after the first token, then the
#: router's handoff sweep streams their KV blocks to a decode-capable
#: replica which activates the stream mid-flight. ``decode`` replicas
#: receive handoffs (and serve full requests only as a fallback when no
#: prefill-capable replica is healthy — availability beats role
#: purity). ``both`` (the default) is the colocated PR-9 behavior.
ROLE_PREFILL, ROLE_DECODE, ROLE_BOTH = "prefill", "decode", "both"
ROLES = (ROLE_PREFILL, ROLE_DECODE, ROLE_BOTH)

#: Default redispatch backoff: immediate-ish (serving latency budgets are
#: milliseconds, not checkpoint-restore seconds), but still exponential
#: so a flapping replica set cannot melt the router in a redispatch storm.
ROUTER_RETRY = RetryPolicy(max_attempts=4, base_delay_s=0.005,
                           backoff=2.0, max_delay_s=0.25, jitter=0.25)

#: Default respawn backoff (ISSUE 10): a DEAD replica's relaunch
#: attempts space out exponentially — a crash-looping worker (bad
#: checkpoint, poisoned cache entry, broken node) must burn its budget
#: slowly instead of melting the router in a spawn storm. Slower than
#: ROUTER_RETRY on purpose: a respawn pays process start + restore +
#: (cached) warmup, not a redispatch.
RESPAWN_RETRY = RetryPolicy(max_attempts=4, base_delay_s=0.05,
                            backoff=2.0, max_delay_s=5.0, jitter=0.25)

#: env default for ``respawn_budget`` (relaunches per replica; 0 = the
#: pre-ISSUE-10 behavior where DEAD is forever)
ROUTER_RESPAWN_ENV = "PTD_ROUTER_RESPAWN"


class ReplicaCrashed(RuntimeError):
    """Raised by a replica's step when the replica is gone (injected
    crash in-process; dead pipe/process for a subprocess worker)."""


#: Global per-op wire timeout override (seconds); per-op overrides ride
#: ``PTD_WIRE_TIMEOUT_<OP>_S`` (op name upper-cased), e.g.
#: ``PTD_WIRE_TIMEOUT_WARMUP_S=120``. Unset → per-op defaults (warmup
#: 600 s; everything else max(hang_grace_s, 30 s)).
WIRE_TIMEOUT_ENV = "PTD_WIRE_TIMEOUT_S"
#: Soft deadline (seconds): any synchronous wire op slower than this
#: emits a ``wire_slow`` telemetry event — a *delayed* op is visible
#: long before the hard timeout declares it a hang.
WIRE_SOFT_ENV = "PTD_WIRE_SOFT_S"


class WireFault(TimeoutError):
    """A protocol-level fault on a replica's wire: a mangled/torn JSON
    line, or a response that never arrived inside its op timeout while
    the worker process is demonstrably alive. Subclasses TimeoutError
    so every existing call site's ``except (ReplicaCrashed,
    TimeoutError)`` contains it — a wire fault can NEVER escape a
    router tick — while new call sites (handoff, dispatch) can catch it
    first and choose quarantine-and-requeue over declare-dead."""

    def __init__(self, msg: str, *, kind: str = "wire_timeout"):
        super().__init__(msg)
        self.kind = kind


class RouterRequest:
    """One client-visible request: the router's durable record of
    everything needed to REDISPATCH the stream losslessly — prompt,
    sampling params (seed included), stop ids, budget, and the tokens
    delivered so far. The engine-side Request handle is disposable (it
    dies with its replica); this one is not."""

    _ids = itertools.count()

    def __init__(self, prompt, max_new_tokens: int,
                 sampling: SamplingParams, stop_ids, on_token=None,
                 deadline_s: float | None = None,
                 tenant: str | None = None, priority: int = 0,
                 kv_window: int | None = None,
                 kv_sink: int | None = None,
                 session_id: str | None = None):
        self.id = next(RouterRequest._ids)
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = max_new_tokens
        self.sampling = sampling
        self.stop_ids = stop_ids
        self.on_token = on_token
        self.deadline_s = deadline_s
        # multi-tenancy (ISSUE 15): the admission controller schedules,
        # rate-limits and sheds by tenant; priority 0 is highest
        self.tenant = tenant or "default"
        self.priority = int(priority)
        # per-request KV limits (tighten-only; the replica's engine
        # clamps to its pool config and may REFUSE incompatible pools)
        self.kv_window = kv_window
        self.kv_sink = kv_sink
        # persistent session (ISSUE 18): the multi-turn identity this
        # stream's KV survives under after the stream closes
        self.session_id = session_id
        self.tokens: list[int] = []          # the delivered stream
        self.done = False
        self.finish_reason: str | None = None
        self.submit_time: float | None = None
        self.first_token_time: float | None = None
        self.finish_time: float | None = None
        self.retries = 0                     # redispatches consumed
        self.replicas: list[int] = []        # placement history
        self._eligible_at = 0.0              # redispatch backoff gate
        self._handle = None                  # engine-side request/mirror
        self._replica: int | None = None
        self._hash_chain: list[str] | None = None  # fleet prefix index
        # distributed tracing (ISSUE 17): the TraceContext minted at
        # router submit (None when tracing is off), the current
        # queue-residency start (reset at every requeue), and the last
        # WDRR dequeue stamp (admission.popleft writes it)
        self.trace = None
        self._trace_enq_t: float | None = None
        self.dequeue_time: float | None = None

    @property
    def output_ids(self) -> np.ndarray:
        """prompt + delivered continuation (int32 [len])."""
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])

    @property
    def ttft_s(self) -> float | None:
        if self.first_token_time is None or self.submit_time is None:
            return None
        return self.first_token_time - self.submit_time


class InProcessReplica:
    """One ServingEngine behind the replica protocol — the CPU test
    tier's replica, and the single-host multi-engine deployment shape.
    Fault application is cooperative (an in-process replica cannot
    os._exit the router): ``apply_fault`` flips flags the step/health
    paths honor, which is exactly what makes the chaos suite
    deterministic."""

    #: extra wall-clock allowance before the router's tick-based hang
    #: watchdog may fire — 0 in-process (the engine steps synchronously
    #: inside router ticks, so a frozen watermark over hang_ticks ticks
    #: IS a hang); subprocess replicas answer asynchronously and set
    #: this > 0 so fast idle router spins can't out-run a healthy
    #: worker's response latency
    hang_grace_s = 0.0
    #: in-process faults are applied by the ROUTER (apply_fault);
    #: subprocess workers run the injector against their own RANK, so
    #: the router must not consult (and consume one-shot markers of)
    #: the same spec on their behalf
    faults_in_worker = False

    def __init__(self, index: int, factory, *, warmup_lens=None):
        self.index = index
        self._factory = factory
        self.engine: ServingEngine = factory()
        self.warmup_lens = warmup_lens
        self.alive = True
        self._hung = False
        self._crash_next = False
        self._slow_ms = 0.0

    def warmup(self, prompt_lens=None, kv_stream: bool = True) -> None:
        self.engine.warmup(prompt_lens=prompt_lens or self.warmup_lens)
        if kv_stream:
            # the KV stream's gather/scatter pair (no-op dense): warmed
            # unconditionally so a handoff or fleet prefix ship never
            # compiles mid-serving
            self.engine.warmup_kv_stream()

    def submit(self, rr: RouterRequest, *, generated, deadline_s,
               on_token, prefill_only: bool = False):
        return self.engine.submit(
            rr.prompt, max_new_tokens=rr.max_new_tokens,
            sampling=rr.sampling, stop_ids=rr.stop_ids,
            deadline_s=deadline_s, generated=generated, on_token=on_token,
            prefill_only=prefill_only,
            kv_window=rr.kv_window, kv_sink=rr.kv_sink,
            session_id=rr.session_id, tenant=rr.tenant,
            trace=rr.trace,
            origin_t=(None if rr.submit_time is None
                      else _trace_to_unix(rr.submit_time)))

    def preempt(self, rr: RouterRequest) -> bool:
        """Evict the stream losslessly (admission-pressure preemption):
        the engine frees its slot/blocks and finishes the handle
        ``"preempted"`` — the router's reap sweep requeues it."""
        return (rr._handle is not None
                and self.engine.preempt_request(rr._handle))

    # -- KV block stream (ISSUE 12) -----------------------------------

    def export_kv(self, rr: RouterRequest):
        return self.engine.export_kv_blocks(rr._handle)

    def import_kv(self, rr: RouterRequest, payload, *, deadline_s,
                  on_token):
        return self.engine.import_kv_blocks(
            payload, on_token=on_token, deadline_s=deadline_s)

    def export_prefix(self, tokens):
        return self.engine.export_prefix_blocks(tokens)

    def import_prefix(self, payload) -> int:
        return self.engine.import_prefix_blocks(payload)

    # -- persistent sessions (ISSUE 18) -------------------------------

    def export_session(self, session_id: str):
        """Pull a RESIDENT parked session off this replica (cross-
        replica reattach: the turn landed elsewhere)."""
        return self.engine.export_session(session_id)

    def seed_session(self, payload) -> int:
        """Seed a session payload into this replica's prefix cache so
        the reattaching submit rides an ordinary prefix hit. Returns
        tokens seeded (0 = declined → re-prefill)."""
        return self.engine.seed_session_blocks(payload, remote=True)

    def take_demoted_sessions(self):
        return self.engine.take_demoted_sessions()

    def step(self) -> None:
        if self._crash_next:
            self.alive = False
            raise ReplicaCrashed(
                f"replica {self.index}: injected crash")
        if self._hung:
            return  # frozen: alive, silent, zero progress
        if self._slow_ms > 0:
            # a straggler, not a hang: the step completes (progress
            # advances, the watchdog stays quiet) — it just takes the
            # injected latency to do so
            time.sleep(self._slow_ms / 1e3)
            self._slow_ms = 0.0
        self.engine.step()

    def health(self) -> dict:
        h = self.engine.health()
        h["alive"] = self.alive
        if self._hung:
            # a wedged device makes no progress but the HOST snapshot
            # still reads fresh — freeze the watermark, as a real hang
            # would
            h["progress"] = -1
        return h

    def probe(self, exclusive: bool = False) -> bool:
        """Device-level params-finite check (the sick tripwire);
        ``exclusive`` is the subprocess wire-scheduling hint — a
        synchronous in-process probe has no wire to share."""
        return self.engine.check_params_finite()

    def apply_fault(self, kind: str, ms: float = 100.0) -> None:
        if kind == "replica_crash":
            self._crash_next = True
        elif kind == "replica_hang":
            self._hung = True
        elif kind == "replica_nan":
            self.poison_params()
        elif kind == "replica_slow":
            self._slow_ms += float(ms)

    def set_draft_params(self, params=None, *, checkpoint=None,
                         step=None) -> dict:
        """Hot-swap the engine's speculative draft weights (ISSUE 16).
        In-process the tree is handed over directly (the router restores
        a checkpoint once for the whole fleet); the engine's structure/
        shape check is the gate. Returns the new draft identity."""
        if params is None:
            if checkpoint is None:
                raise ValueError("pass params or checkpoint")
            from pytorchdistributed_tpu.training.checkpoint import (
                CheckpointManager,
            )

            with CheckpointManager(checkpoint) as mgr:
                params, _ = mgr.restore_params(step=step)
        self.engine.set_draft_params(params)
        return {"draft_hash": self.engine.draft_params_hash(),
                "draft_swaps": self.engine.draft_swaps}

    def poison_params(self) -> None:
        """NaN every inexact param leaf (engine.nan_params): outputs
        rot instantly, and only the params-finite tripwire can say
        why."""
        from pytorchdistributed_tpu.serving.engine import nan_params

        self._saved_weights = self.engine._weights
        self.engine.set_params(nan_params(self.engine._weights))

    def restore_params(self) -> None:
        """The operator's repair step (tests: undo poison_params) —
        rejoin still requires the router's probe streak + canary."""
        if getattr(self, "_saved_weights", None) is not None:
            self.engine.set_params(self._saved_weights)
            self._saved_weights = None

    def quarantine_reset(self) -> None:
        """Entering quarantine: retire resident garbage streams (the
        router already redispatched them) and drop every cached prefix
        block — K/V written under NaN params must never serve a future
        prefix hit."""
        self.engine.drain()
        self.engine.invalidate_prefix_cache()

    def drain(self) -> list:
        return self.engine.drain()

    def close(self) -> None:
        if self.alive and not self._hung:
            self.engine.close()


class _Mirror:
    """Router-side stand-in for a request living in a subprocess
    worker: done/finish_reason arrive in step replies; ``parked``
    flips when the worker reports the request prefilled-and-parked
    (the handoff sweep's trigger)."""

    done = False
    finish_reason = None
    parked = False


class SubprocessReplica:
    """One replica as a SEPARATE PROCESS (`python -m pytorchdistributed_
    tpu.serving.replica_worker`), spawned with the same env contract
    run.py gives training workers — RANK (the replica index),
    WORLD_SIZE, MASTER_ADDR/MASTER_PORT, PTD_HEARTBEAT_DIR /
    PTD_TELEMETRY_DIR / PTD_FAULTS pass-through — and driven over a
    line-JSON stdin/stdout protocol with AT MOST ONE op in flight.

    The async single-outstanding-op design is what makes hang detection
    honest: the router never blocks on a wedged worker — a step op's
    response simply fails to arrive, the progress watermark stalls, and
    the watchdog fires after ``hang_ticks`` router ticks, exactly like
    the in-process path. Death is immediate: process exit or pipe EOF
    raises ReplicaCrashed at the next interaction. Teardown forwards
    SIGTERM and escalates through run.py's ``kill_group`` — a drained
    router can never leave an orphan worker."""

    faults_in_worker = True
    #: router-installed ChaosSchedule (or None): consulted on every
    #: received line so wire faults hit the real recv path, not a mock
    wire_chaos = None
    #: router-installed event sink: ``on_wire_event(event, **row)`` —
    #: wire_fault / wire_slow / wire_retry / wire_timeout land in the
    #: router telemetry stream with the replica index stamped
    on_wire_event = None
    #: hard-timeout defaults per op (seconds); anything absent falls
    #: back to max(hang_grace_s, 30). Env overrides: WIRE_TIMEOUT_ENV
    #: globally, ``PTD_WIRE_TIMEOUT_<OP>_S`` per op.
    OP_TIMEOUTS_S = {"warmup": 600.0, "set_draft_params": 60.0,
                     "drain": 60.0}

    def __init__(self, index: int, spec: dict, *, world_size: int = 1,
                 env: dict | None = None, hang_grace_s: float = 10.0,
                 heartbeat_dir: str | None = None,
                 master_port: int | None = None):
        from pytorchdistributed_tpu.run import free_port

        self.index = index
        self.hang_grace_s = hang_grace_s
        self._mirrors: dict[int, object] = {}
        self._on_token: dict[int, object] = {}
        # the run.py liveness contract: the worker touches
        # rank<index> after every step's host sync; health() surfaces
        # the age next to the protocol-level progress watermark
        self.heartbeat_path = (
            os.path.join(heartbeat_dir, f"rank{index}")
            if heartbeat_dir else None)
        self.alive = True
        self._health: dict = {"alive": True, "progress": -1, "active": 0,
                              "queued": 0, "free_slots": 0,
                              "prefilling": 0, "num_slots": 1,
                              "occupancy": 0.0, "pool_free_frac": 1.0,
                              "ttft_ema_s": None, "sick": False}
        self._pending_op: str | None = None
        self._probe_result: bool | None = None
        # wire-protocol fault accounting (ISSUE 19): bad lines never
        # raise out of recv — they set the flag the router's health
        # sweep converts into a quarantine
        self.protocol_faults = 0
        self._protocol_fault = False
        self.wire_stats: dict[str, int] = collections.Counter()
        # session payloads demoted by the worker, awaiting the router's
        # store-persist sweep: [(sid, tenant, wire_payload), ...]
        self._demoted: list = []
        full_env = dict(os.environ)
        if env:
            full_env.update(env)
        full_env.update(self._chip_env(index))
        full_env.update({
            "RANK": str(index), "LOCAL_RANK": str(index),
            "WORLD_SIZE": str(world_size),
            "MASTER_ADDR": "localhost",
            # ONE port shared by the whole worker fleet (the run.py
            # group contract): a future cross-replica rendezvous must
            # find every rank agreeing on it
            "MASTER_PORT": str(master_port if master_port is not None
                               else free_port()),
            "PTD_REPLICA_SPEC": json.dumps(spec),
        })
        if heartbeat_dir:
            from pytorchdistributed_tpu.runtime.heartbeat import (
                HEARTBEAT_DIR_ENV,
            )

            os.makedirs(heartbeat_dir, exist_ok=True)
            full_env[HEARTBEAT_DIR_ENV] = heartbeat_dir
        self.proc = subprocess.Popen(
            [sys.executable, "-m",
             "pytorchdistributed_tpu.serving.replica_worker"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            env=full_env, text=True, bufsize=1)

    @staticmethod
    def _chip_env(index: int) -> dict[str, str]:
        """One process for each chip (runtime/launch.py): on a TPU host
        worker ``index`` is shown chip ``index`` alone. Refused — rather
        than left to fail or hang — when this process has already taken
        the chips by touching JAX, or when there is no chip ``index``."""
        from pytorchdistributed_tpu.runtime.launch import (
            local_tpu_chips,
            one_chip_env,
        )

        chips = local_tpu_chips()
        if chips == 0:
            return {}
        jax = sys.modules.get("jax")
        if jax is not None and jax._src.xla_bridge.backends_are_initialized():
            raise RuntimeError(
                f"replica {index}: this process has initialised JAX and "
                f"holds the host's {chips} TPU chip(s), so a worker "
                f"process cannot take one — keep the router's process off "
                f"JAX, or serve with InProcessReplica (one process, one "
                f"replica per chip)")
        if index >= chips:
            raise RuntimeError(
                f"replica {index}: the host has {chips} TPU chip(s) and "
                f"one worker process drives one chip")
        return one_chip_env(index)

    # -- wire ---------------------------------------------------------

    def _send(self, op: dict) -> None:
        if not self.alive or self.proc.poll() is not None:
            self.alive = False
            raise ReplicaCrashed(f"replica {self.index}: worker exited "
                                 f"(code {self.proc.poll()})")
        try:
            self.proc.stdin.write(json.dumps(op) + "\n")
            self.proc.stdin.flush()
        except (BrokenPipeError, OSError) as e:
            self.alive = False
            raise ReplicaCrashed(
                f"replica {self.index}: pipe broke ({e})") from None
        self._pending_op = op["op"]
        self._last_sent = op["op"]

    def _try_recv(self, timeout: float = 0.0) -> dict | None:
        """Non-blocking (or bounded) read of the pending response; None
        when the worker hasn't answered yet — the router moves on and
        the watermark records the silence. A line that fails to parse
        is a PROTOCOL FAULT, not an exception: the flag is set, the
        line dropped, and the router's health sweep quarantines the
        replica through the ordinary clean-probe→canary path."""
        if self._pending_op is None:
            return None
        r, _, _ = select.select([self.proc.stdout], [], [], timeout)
        if not r:
            if self.proc.poll() is not None:
                self.alive = False
                raise ReplicaCrashed(
                    f"replica {self.index}: worker exited "
                    f"(code {self.proc.poll()})")
            return None
        line = self.proc.stdout.readline()
        if not line:
            self.alive = False
            raise ReplicaCrashed(f"replica {self.index}: EOF "
                                 f"(code {self.proc.poll()})")
        if self.wire_chaos is not None:
            line, fault = self.wire_chaos.mangle_recv(self.index, line)
            if fault is not None:
                self.wire_stats[fault] += 1
                if self.on_wire_event is not None:
                    self.on_wire_event("wire_fault", fault=fault,
                                       op=self._pending_op)
            if line is None:
                # wire_drop: the response is simply GONE. The op stays
                # pending — exactly what real message loss looks like —
                # and surfaces through wait_response's timeout or the
                # tick loop's progress watermark.
                return None
        op = self._pending_op
        self._pending_op = None
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            self.protocol_faults += 1
            self._protocol_fault = True
            self.wire_stats["bad_lines"] += 1
            sys.stderr.write(
                f"[router] replica {self.index}: unparseable wire line "
                f"for op {op!r} ({len(line)} bytes) — protocol fault\n")
            return None

    def _op_timeout(self, op: str | None) -> float:
        """Hard response deadline for ``op``: per-op env override >
        global env override > per-op default > max(hang_grace_s, 30)."""
        if op:
            v = os.environ.get(f"PTD_WIRE_TIMEOUT_{op.upper()}_S")
            if v:
                return float(v)
        v = os.environ.get(WIRE_TIMEOUT_ENV)
        if v:
            return float(v)
        base = self.OP_TIMEOUTS_S.get(op or "")
        if base is None:
            return max(self.hang_grace_s, 30.0)
        return max(self.hang_grace_s, base)

    def wait_response(self, timeout: float | None = None, *,
                      op: str | None = None, retries: int = 1) -> dict:
        """Blocking receive for the synchronous phases (warmup, close,
        handoffs) where the caller legitimately waits — never used in
        the steady-state tick loop. ``timeout=None`` resolves the
        per-op policy (``_op_timeout``); crossing the soft deadline
        emits one ``wire_slow`` event (a DELAYED op is observable long
        before it times out); a hard timeout with the worker still
        alive grants ``retries`` extra window(s) (``wire_retry``)
        before giving up with WireFault (``wire_timeout``) — a torn
        line observed while waiting raises WireFault immediately."""
        op = op or self._pending_op
        if timeout is None:
            timeout = self._op_timeout(op)
        soft = float(os.environ.get(WIRE_SOFT_ENV, "5.0"))
        faults_before = self.protocol_faults
        start = time.perf_counter()
        deadline = start + timeout
        soft_fired = False
        retries_left = max(0, int(retries))
        while True:
            resp = self._try_recv(timeout=0.2)
            if self.protocol_faults > faults_before:
                raise WireFault(
                    f"replica {self.index}: protocol fault while "
                    f"waiting for {op!r}", kind="wire_protocol")
            if resp is not None:
                return resp
            now = time.perf_counter()
            if not soft_fired and now - start > soft:
                soft_fired = True
                if self.on_wire_event is not None:
                    self.on_wire_event("wire_slow", op=op,
                                       waited_s=round(now - start, 3))
            if now > deadline:
                if retries_left > 0 and self.proc.poll() is None:
                    retries_left -= 1
                    deadline = now + min(timeout, 5.0)
                    self.wire_stats["retries"] += 1
                    if self.on_wire_event is not None:
                        self.on_wire_event("wire_retry", op=op,
                                           waited_s=round(now - start, 3))
                    continue
                if self.on_wire_event is not None:
                    self.on_wire_event("wire_timeout", op=op,
                                       waited_s=round(now - start, 3))
                raise WireFault(
                    f"replica {self.index}: no response within "
                    f"{timeout}s (op {op})")

    # -- replica protocol ---------------------------------------------

    def warmup(self, prompt_lens=None, kv_stream: bool = True) -> None:
        self._send({"op": "warmup",
                    "prompt_lens": list(prompt_lens or []),
                    "kv_stream": bool(kv_stream)})
        # first warmup pays the worker's jax import + compiles; the
        # default 600 s hard deadline is env-tunable (WIRE_TIMEOUT_ENV /
        # PTD_WIRE_TIMEOUT_WARMUP_S)
        self._consume(self.wait_response(op="warmup"))

    def warmup_async(self, prompt_lens=None, kv_stream: bool = True
                     ) -> None:
        """Send the warmup op WITHOUT waiting — the respawn path
        (ISSUE 10): a replacement worker's startup (jax import +
        checkpoint restore + cached warmup) must not stall the router's
        tick loop. While ``_warming``, probe() reports un-ready, so the
        quarantine machine keeps the replica parked; the warmup reply
        is consumed by the probe path's receive whenever it lands."""
        self._warming = True
        self._send({"op": "warmup",
                    "prompt_lens": list(prompt_lens or []),
                    "kv_stream": bool(kv_stream)})

    def submit(self, rr: RouterRequest, *, generated, deadline_s,
               on_token, prefill_only: bool = False):
        self._drain_wire()
        op = {"op": "submit", "rid": rr.id,
              "prompt": rr.prompt.tolist(),
              "max_new_tokens": rr.max_new_tokens,
              "sampling": {
                  "temperature": rr.sampling.temperature,
                  "top_k": rr.sampling.top_k,
                  "top_p": rr.sampling.top_p,
                  "seed": rr.sampling.seed},
              "stop_ids": list(rr.stop_ids),
              "generated": list(generated or []),
              "deadline_s": deadline_s,
              "prefill_only": bool(prefill_only),
              "kv_window": rr.kv_window,
              "kv_sink": rr.kv_sink}
        # session identity rides only when set, keeping the off-wire
        # byte-identical to pre-session traffic
        if rr.session_id is not None:
            op["session_id"] = rr.session_id
            op["tenant"] = rr.tenant
        # origin submit + trace identity (ISSUE 17): unix-epoch and a
        # plain dict so the worker needs no shared clock or objects;
        # trace keys ride only when tracing minted a context, so the
        # off-wire is byte-identical to pre-ISSUE-17 traffic minus the
        # always-on origin stamp (the TTFT-e2e bugfix is not gated on
        # tracing)
        if rr.submit_time is not None:
            op["origin_t"] = _trace_to_unix(rr.submit_time)
        if rr.trace is not None:
            op["trace"] = rr.trace.to_wire()
        self._send(op)
        self._on_token[rr.id] = on_token
        m = _Mirror()
        self._mirrors[rr.id] = m
        return m

    def preempt(self, rr: RouterRequest) -> bool:
        """Synchronous preempt roundtrip (rare — admission pressure
        only, so the one-in-flight wire cost is acceptable, same as a
        KV handoff). The worker's reply is consumed HERE, not through
        ``_consume`` — an ok=False preempt must not be mistaken for a
        submit refusal and fail a perfectly live stream."""
        self._drain_wire()
        self._send({"op": "preempt", "rid": rr.id})
        resp = self.wait_response(op="preempt")
        self._pending_op = None
        if not resp.get("ok"):
            return False
        m = self._mirrors.pop(rr.id, None)
        if m is not None:
            m.done, m.finish_reason = True, "preempted"
        self._on_token.pop(rr.id, None)
        return True

    def set_draft_params(self, params=None, *, checkpoint=None,
                         step=None) -> dict:
        """Draft hot-swap over the wire (ISSUE 16): the payload is a
        CHECKPOINT PATH, never a weight tree — the worker restores it
        locally through the same manifest-verified path as its boot
        weights, and the engine's structure/shape check accepts or
        refuses. Synchronous roundtrip (rare, like a handoff); a
        refusal raises ValueError with the worker's reason."""
        if checkpoint is None:
            raise ValueError(
                "subprocess replicas take set_draft_params(checkpoint=...)"
                " — weight trees do not cross the wire")
        self._drain_wire()
        self._send({"op": "set_draft_params",
                    "checkpoint": str(checkpoint),
                    "step": step})
        resp = self.wait_response(op="set_draft_params")
        self._pending_op = None
        if resp.get("ok") is not True:
            raise ValueError(
                f"replica {self.index}: set_draft_params refused: "
                f"{resp.get('error')}")
        return {"draft_hash": resp.get("draft_hash"),
                "draft_swaps": int(resp.get("draft_swaps", 0))}

    # -- KV block stream (ISSUE 12) -----------------------------------
    # Handoffs are synchronous wire roundtrips by design: the payload
    # op and its reply must not interleave with step traffic (the
    # one-in-flight invariant), and a handoff is rare relative to
    # ticks. A wedged worker surfaces as TimeoutError — the caller's
    # dead-replica path, same as submit.

    def export_kv(self, rr: RouterRequest):
        self._drain_wire()
        self._send({"op": "export_kv", "rid": rr.id})
        resp = self.wait_response(op="export_kv")
        if resp.get("ok") is not True or not resp.get("payload"):
            raise ValueError(
                f"replica {self.index}: export_kv({rr.id}) refused: "
                f"{resp.get('error')}")
        self._mirrors.pop(rr.id, None)
        self._on_token.pop(rr.id, None)
        return kv_payload_from_wire(resp["payload"])

    def import_kv(self, rr: RouterRequest, payload, *, deadline_s,
                  on_token):
        self._drain_wire()
        self._send({"op": "import_kv", "rid": rr.id,
                    "deadline_s": deadline_s,
                    "payload": kv_payload_to_wire(payload)})
        resp = self.wait_response(op="import_kv")
        if resp.get("ok") is not True:
            return None  # no capacity / mismatch: resume-from-tokens
        m = _Mirror()
        self._mirrors[rr.id] = m
        self._on_token[rr.id] = on_token
        return m

    def export_prefix(self, tokens):
        self._drain_wire()
        self._send({"op": "export_prefix",
                    "tokens": [int(t) for t in tokens]})
        resp = self.wait_response(op="export_prefix")
        if resp.get("ok") is not True or not resp.get("payload"):
            return None
        return prefix_payload_from_wire(resp["payload"])

    def import_prefix(self, payload) -> int:
        self._drain_wire()
        self._send({"op": "import_prefix",
                    "payload": prefix_payload_to_wire(payload)})
        resp = self.wait_response(op="import_prefix")
        return int(resp.get("adopted", 0)) if resp.get("ok") else 0

    # -- persistent sessions (ISSUE 18) -------------------------------
    # Like handoffs, session pulls/seeds are synchronous roundtrips:
    # rare relative to ticks, and the payload must not interleave with
    # step traffic on the one-in-flight wire.

    def export_session(self, session_id: str):
        self._drain_wire()
        self._send({"op": "export_session", "session_id": session_id})
        resp = self.wait_response(op="export_session")
        if resp.get("ok") is not True or not resp.get("payload"):
            return None
        return kv_payload_from_wire(resp["payload"])

    def seed_session(self, payload) -> int:
        self._drain_wire()
        self._send({"op": "seed_session",
                    "payload": kv_payload_to_wire(payload)})
        resp = self.wait_response(op="seed_session")
        return int(resp.get("seeded", 0)) if resp.get("ok") else 0

    def take_demoted_sessions(self):
        """Drain session payloads the worker demoted (reported in step
        replies) — the router persists them into the store tiers."""
        out, self._demoted = self._demoted, []
        return [(sid, tenant, kv_payload_from_wire(wire))
                for sid, tenant, wire in out]

    def _drain_wire(self, timeout: float | None = None) -> None:
        """Consume the pending response (if any) before sending a new
        op — the one-in-flight invariant. Only submit/drain/close use
        it; the steady-state step path is fully non-blocking. The
        default bound is ``hang_grace_s``: a healthy worker answers in
        milliseconds, and a wedged one must not stall the whole router
        longer than the hang watchdog would have tolerated anyway (the
        TimeoutError surfaces as a dead-replica declaration)."""
        if self._pending_op is not None:
            resp = self.wait_response(
                self.hang_grace_s if timeout is None else timeout)
            self._consume(resp)

    def _consume(self, resp: dict) -> None:
        if resp.get("ok") is False and "rid" in resp:
            # the worker REFUSED the submit (validation error): the
            # request is terminal — redispatching it would only collect
            # the same refusal fleet-wide
            m = self._mirrors.pop(resp["rid"], None)
            if m is not None:
                m.done, m.finish_reason = True, "failed"
            self._on_token.pop(resp["rid"], None)
            return
        if "max_seq_len" in resp:
            self.reported_max_seq_len = int(resp["max_seq_len"])
            self._warming = False  # the async-warmup reply landed
        if resp.get("health"):
            self._health = resp["health"]
            self._health["alive"] = True
        for rid, tok in resp.get("delivered", []):
            cb = self._on_token.get(rid)
            if cb is not None:
                cb(rid, tok)
        for rid in resp.get("parked", []):
            m = self._mirrors.get(rid)
            if m is not None:
                m.parked = True
        for item in resp.get("demoted_sessions", []):
            self._demoted.append(tuple(item))
        for rid, reason in resp.get("finished", []):
            m = self._mirrors.pop(rid, None)
            if m is not None:
                m.done, m.finish_reason = True, reason
            # drop the per-request closure too, or a long-lived worker
            # retains every RouterRequest it ever served
            self._on_token.pop(rid, None)
        if "finite" in resp:
            self._probe_result = bool(resp["finite"])

    def step(self) -> None:
        """One async protocol turn: collect whatever the worker answered
        since last tick, then (if the wire is idle) send the next step
        op. No response → no progress recorded → the hang watchdog's
        evidence accumulates."""
        resp = self._try_recv()
        if resp is not None:
            self._consume(resp)
        if self._pending_op is None:
            self._send({"op": "step"})

    def health(self) -> dict:
        h = dict(self._health)
        h["alive"] = self.alive
        if self.heartbeat_path is not None:
            from pytorchdistributed_tpu.runtime.heartbeat import (
                last_beat_age,
            )

            h["heartbeat_age_s"] = last_beat_age(self.heartbeat_path)
        return h

    def probe(self, exclusive: bool = False) -> bool:
        """Params-finite probe over the wire. Answered asynchronously:
        returns the LAST verdict (optimistically True before the first
        answer arrives) and keeps the pipeline moving. RECEIVE before
        deciding to send: the steady-state loop always leaves a step op
        pending, so a send-first probe would be skipped every single
        time and a NaN'd worker would never be caught. Never send two
        probes back to back: at health_every=1 that would monopolize
        the one-in-flight wire and STARVE the step ops — probe and step
        alternate instead. ``exclusive=True`` (a QUARANTINED replica,
        which is never stepped, so probes are the only traffic) lifts
        the alternation."""
        with span("serve/probe"):
            resp = self._try_recv()
            if resp is not None:
                self._consume(resp)
            if getattr(self, "_warming", False):
                # async-respawn startup in flight: not ready is the honest
                # verdict (the optimistic True below would let the rejoin
                # streak run out before the worker can even serve)
                return False
            if (self._pending_op is None
                    and (exclusive
                         or getattr(self, "_last_sent", None) != "probe")):
                self._send({"op": "probe"})
            return (self._probe_result if self._probe_result is not None
                    else True)

    def apply_fault(self, kind: str, ms: float = 100.0) -> None:
        """One-shot tick-targeted faults ride PTD_FAULTS into the
        worker itself (it runs the injector against its own RANK), but
        RATE-BASED chaos decisions live router-side (the ChaosSchedule
        is seeded once, in one process) — so the router plays the
        cluster: crash kills the process, hang SIGSTOPs it (alive,
        silent — the watchdog's problem), nan/slow ride a wire op the
        worker applies to its own engine."""
        import signal as _signal

        if kind == "replica_crash":
            self.proc.kill()
        elif kind == "replica_hang":
            try:
                os.kill(self.proc.pid, _signal.SIGSTOP)
            except (OSError, ProcessLookupError):
                pass
        elif kind in ("replica_nan", "replica_slow"):
            try:
                self._drain_wire()
                self._send({"op": "inject", "kind": kind,
                            "ms": float(ms)})
                self.wait_response(op="inject")
                self._pending_op = None
            except (ReplicaCrashed, TimeoutError):
                pass  # the health sweep owns the diagnosis

    def quarantine_reset(self) -> None:
        try:
            self._drain_wire()
            self._send({"op": "drain"})
            self._consume(self.wait_response(op="drain"))
        except WireFault:
            # the wire hiccuped DURING the reset: the replica is
            # already quarantined — the probe streak decides its fate,
            # no need to escalate a torn line into a death sentence
            pass
        except (ReplicaCrashed, TimeoutError):
            self.alive = False

    def drain(self) -> list:
        self.quarantine_reset()
        return []

    def close(self, grace: float = 10.0) -> None:
        """Graceful protocol close, then the run.py teardown escalation
        (SIGTERM → SIGCONT → SIGKILL after grace) — no orphans, even if
        the worker is wedged or SIGSTOPped."""
        from pytorchdistributed_tpu.run import kill_group

        if self.alive and self.proc.poll() is None:
            try:
                self._drain_wire(timeout=5.0)
                self._send({"op": "close"})
            except (ReplicaCrashed, TimeoutError):
                pass
        kill_group([self.proc], grace=grace)
        self.alive = False
        for pipe in (self.proc.stdin, self.proc.stdout):
            try:
                pipe.close()
            except OSError:
                pass


class ReplicaRouter:
    """The health-checked, failover-capable front of N serving replicas.

    Construction (pick one):
      * ``ReplicaRouter(model, params, replicas=N, engine_kwargs={...})``
        — N in-process ServingEngines over shared weights (they also
        share the jit cache: N replicas compile once);
      * ``ReplicaRouter(factories=[...])`` — explicit per-replica
        engine factories (different pool sizes, meshes, ...);
      * ``ReplicaRouter(workers=[spec, ...])`` — subprocess replicas:
        each spec is a replica_worker model/engine description, each
        worker is launched under the run.py env contract.

    Knobs:
      roles: one of ROLE_PREFILL / ROLE_DECODE / ROLE_BOTH per replica
        (ISSUE 12) — None means all ``both`` (the colocated default).
        With any split role, new requests dispatch to prefill-capable
        replicas as ``prefill_only`` admissions; the handoff sweep
        streams each parked request's KV blocks to the decode-capable
        replica the health scorer picks, which activates the stream
        mid-flight (bitwise-equal to colocated — the blocks carry
        exact K/V). Any handoff failure falls back to resume-from-
        tokens redispatch, so disaggregation can only cost a re-
        prefill, never a stream. Independently of roles, the router
        keeps a fleet-wide prefix index over every replica's published
        radix frontier: the dispatcher steers prefix-sharing requests
        to the deepest match, shipping the owner's cached blocks to
        the chosen replica when they differ.
      max_queue: router admission bound — a submit arriving with this
        many requests already queued is SHED immediately
        (``finish_reason="shed"``): bounded latency for everyone
        admitted beats unbounded latency for everyone.
      max_retries: redispatches a single request may consume before it
        is failed (``finish_reason="failed"``) — the retry budget.
      retry_policy: faults/retry.py backoff between a request's
        redispatches (default ROUTER_RETRY: ms-scale, exponential,
        jittered).
      hang_ticks: consecutive router ticks a replica may hold work
        without moving its progress watermark before it is declared
        hung — the watchdog bound (detection latency ≤ hang_ticks
        ticks, asserted in the chaos suite).
      health_every: params-finite probe cadence in ticks (the probe is
        one compiled scalar reduction; every tick would double the
        tick's device dispatches for tiny models).
      rejoin_after: consecutive CLEAN probes a quarantined replica
        needs before the warmup canary + re-admission.
      respawn_budget: relaunches each DEAD replica may consume
        (ISSUE 10; default the PTD_ROUTER_RESPAWN env, else 0 = DEAD
        is forever). A crashed/hung replica is rebuilt — subprocess
        workers relaunch under the same spec/env contract (a
        ``"checkpoint"`` spec restores verified weights), in-process
        replicas re-run their
        engine factory — then rejoins through the EXISTING
        quarantine → clean-probe → canary path, so a recovered
        replica proves itself before real traffic returns. Its
        former streams were already failed over; respawn restores
        CAPACITY, turning a crash into a transient instead of a
        permanent fleet shrink.
      respawn_policy: faults/retry.py backoff between one replica's
        relaunch attempts (default RESPAWN_RETRY: exponential,
        jittered, capped at seconds).
      respawn_warmup_s: startup bound for a respawned subprocess
        worker's ASYNC warmup — past it the replacement is declared
        hung and the next budgeted attempt proceeds (mirrors the
        synchronous warmup()'s 600 s response timeout).
      faults: a FaultInjector, None to disable chaos entirely, or
        "auto" (default: the process-global ``faults.active()`` —
        the PTD_FAULTS contract).
      telemetry / telemetry_dir: RouterTelemetry sink (per-replica
        rows + event rows + close-time summary).
      seed: the jitter RNG for redispatch backoff (deterministic
        schedules for the chaos suite).
    """

    def __init__(self, model=None, params=None, *, replicas: int = 2,
                 engine_kwargs: dict | None = None, factories=None,
                 workers=None, warmup_lens=None, roles=None,
                 max_queue: int | None = None, max_retries: int = 2,
                 retry_policy: RetryPolicy = ROUTER_RETRY,
                 hang_ticks: int = 8, health_every: int = 4,
                 rejoin_after: int = 3, max_pending: int = 1,
                 respawn_budget: int | None = None,
                 respawn_policy: RetryPolicy = RESPAWN_RETRY,
                 respawn_warmup_s: float = 600.0,
                 faults="auto", telemetry: RouterTelemetry | None = None,
                 telemetry_dir=None, sample_every: int = 1,
                 tenants=None, admission=None,
                 preempt_every: int = 8, seed: int = 0,
                 trace="auto", slo_ttft_s: float | None = None,
                 session_store=None):
        self.warmup_lens = tuple(warmup_lens) if warmup_lens else None
        # distributed request tracing (ISSUE 17): OFF unless asked —
        # trace=True (needs telemetry_dir for the files), a
        # RequestTracer instance, or the default "auto" which honors
        # the PTD_TRACE env contract (so subprocess fleets flip one
        # env var and every worker's tracer comes up with the router's).
        # In-process engines SHARE this tracer (one process, one file);
        # subprocess workers build their own per-RANK one from the env.
        if isinstance(trace, RequestTracer):
            self.trace = trace
        elif trace is True:
            if telemetry_dir is None:
                raise ValueError(
                    "trace=True needs telemetry_dir= — the per-rank "
                    "trace_rank*.jsonl files land there")
            self.trace = RequestTracer(
                telemetry_dir, rank="router",
                **({} if slo_ttft_s is None
                   else {"slo_ttft_s": slo_ttft_s}))
        elif trace == "auto" and telemetry_dir is not None \
                and os.environ.get("PTD_TRACE", "0").lower() in (
                    "1", "true", "yes", "on"):
            self.trace = RequestTracer(
                telemetry_dir, rank="router",
                **({} if slo_ttft_s is None
                   else {"slo_ttft_s": slo_ttft_s}))
        else:
            self.trace = None
        self._hb_dir = None
        self._worker_specs = None
        self._worker_port = None
        self._worker_env = None
        self._factory_fn = None
        if workers is not None:
            import tempfile

            from pytorchdistributed_tpu.run import free_port

            # one liveness dir + ONE master port for the worker fleet
            # (the run.py group env contract); dir removed at close().
            # spec list + port kept: respawn relaunches a DEAD worker
            # under the exact same contract
            self._hb_dir = tempfile.mkdtemp(prefix="ptd_router_hb_")
            port = free_port()
            self._worker_specs = list(workers)
            # scale-up template: a new replica index i reuses spec
            # i % len(base) — homogeneous fleets (the common case) just
            # clone spec 0
            self._base_specs = list(workers)
            self._worker_port = port
            # a programmatic trace=True must reach the workers too —
            # export the same env contract the "auto" path reads, so
            # every worker's RequestTracer.from_env comes up
            if self.trace is not None:
                self._worker_env = {
                    "PTD_TRACE": "1",
                    TELEMETRY_DIR_ENV: self.trace.run_dir}
            self._replicas = [
                SubprocessReplica(i, spec, world_size=len(workers),
                                  heartbeat_dir=self._hb_dir,
                                  master_port=port,
                                  env=self._worker_env)
                for i, spec in enumerate(workers)]
            self.max_seq_len = min(
                int(s.get("max_seq_len",
                          s.get("overrides", {}).get("max_seq_len",
                                                     1 << 30)))
                for s in workers)
        else:
            if factories is None:
                if model is None or params is None:
                    raise ValueError(
                        "pass (model, params), factories=, or workers=")
                kw = dict(engine_kwargs or {})
                # with a telemetry_dir, each engine gets its own
                # ServingTelemetry at rank=replica-index, so the
                # serve_metrics/span files land per replica (the report
                # CLI's serving table then reads as a per-replica
                # table) instead of being silently dropped
                wire_tele = (telemetry_dir is not None
                             and "telemetry" not in kw
                             and "telemetry_dir" not in kw)
                # in-process engines emit request spans through the
                # ROUTER's tracer (same process, same clock, one file)
                wire_trace = self.trace is not None and "trace" not in kw

                def make_factory(i):
                    def factory():
                        ekw = dict(kw)
                        if wire_tele:
                            from pytorchdistributed_tpu.serving.telemetry \
                                import ServingTelemetry

                            ekw["telemetry"] = ServingTelemetry(
                                telemetry_dir, rank=i)
                        if wire_trace:
                            ekw["trace"] = self.trace
                        return ServingEngine(model, params, **ekw)
                    return factory

                factories = [make_factory(i) for i in range(replicas)]
                self._factory_fn = make_factory
            else:
                factories = list(factories)
                self._factory_fn = (
                    lambda i, fs=factories: fs[i % len(fs)])
            self._replicas = [
                InProcessReplica(i, f, warmup_lens=self.warmup_lens)
                for i, f in enumerate(factories)]
            self.max_seq_len = min(
                r.engine.cfg.max_seq_len for r in self._replicas)
        if not self._replicas:
            raise ValueError("need at least one replica")
        if roles is None:
            roles = [ROLE_BOTH] * len(self._replicas)
        roles = list(roles)
        if len(roles) != len(self._replicas):
            raise ValueError(
                f"roles has {len(roles)} entries for "
                f"{len(self._replicas)} replicas")
        for role in roles:
            if role not in ROLES:
                raise ValueError(f"unknown role {role!r} (want one of "
                                 f"{ROLES})")
        self._roles = roles
        self._disagg = any(role != ROLE_BOTH for role in roles)
        if self._disagg and not any(
                role in (ROLE_DECODE, ROLE_BOTH) for role in roles):
            raise ValueError(
                "a disaggregated topology needs at least one decode-"
                "capable replica (role 'decode' or 'both') to receive "
                "KV handoffs")
        # the fleet-wide prefix index (ISSUE 12): every replica's
        # published radix frontier, refreshed from health snapshots
        self._prefix_index = FleetPrefixIndex()
        # the fleet-wide session index (ISSUE 18): session → owning
        # replica, refreshed from the same health snapshots; with a
        # SessionStore attached, demoted sessions flow into the host-
        # DRAM/disk tiers and reattaching turns are pulled back up
        self._session_index = FleetSessionIndex()
        self.session_store = session_store
        self.max_queue = max_queue
        self.max_retries = max_retries
        self.retry_policy = retry_policy
        self.hang_ticks = max(1, hang_ticks)
        self.health_every = max(1, health_every)
        self.rejoin_after = max(1, rejoin_after)
        self.max_pending = max(0, max_pending)
        if respawn_budget is None:
            respawn_budget = int(os.environ.get(ROUTER_RESPAWN_ENV, "0"))
        self.respawn_budget = max(0, respawn_budget)
        self.respawn_policy = respawn_policy
        self.respawn_warmup_s = respawn_warmup_s
        self._respawns = [0 for _ in self._replicas]
        self._respawn_eligible = [0.0 for _ in self._replicas]
        self._warming_deadline = [0.0 for _ in self._replicas]
        # "auto" = the process-global PTD_FAULTS contract; None = chaos
        # explicitly off; or a FaultInjector
        self._faults = (faults_inject.active() if faults == "auto"
                        else faults)
        if (self._faults is not None
                and not hasattr(self._faults, "mangle_recv")):
            # a plain injector whose plan carries wire or rate/period
            # specs needs the ChaosSchedule machinery — upgrade in
            # place so `PTD_FAULTS="wire_torn@rate=0.1" just works
            plan = getattr(self._faults, "plan", None)
            if plan is not None and any(
                    s.kind in faults_inject._WIRE_KINDS
                    or s.rate is not None or s.period is not None
                    for s in plan.specs):
                from pytorchdistributed_tpu.faults.chaos import (
                    ChaosSchedule,
                )

                self._faults = ChaosSchedule(
                    plan, seed=seed, rank=self._faults.rank,
                    state_dir=self._faults.state_dir,
                    events=self._faults.events)
        self._rng = random.Random(seed)
        if telemetry is None:
            # no dir -> RING-ONLY telemetry: zero files, but the signal
            # rings / recent-events the autoscaler consumes always exist
            telemetry = RouterTelemetry(telemetry_dir)
        self.telemetry = telemetry
        self.sample_every = max(1, sample_every)
        # multi-tenant admission (ISSUE 15): when tenants/admission is
        # given, the router queue IS the AdmissionController — it speaks
        # the deque protocol (append/appendleft/popleft/remove/iter), so
        # every existing queue path (dispatch, failover requeue,
        # deadline expiry, drain) runs unchanged, but popleft order is
        # priority-tiered weighted deficit round-robin and submit goes
        # through offer()'s rate caps + weighted shedding
        self._admission = None
        if admission is not None or tenants:
            from pytorchdistributed_tpu.serving.admission import (
                AdmissionController,
            )

            if admission is None:
                admission = AdmissionController(tenants,
                                                max_queue=max_queue)
            self._admission = admission
            self._queue = admission
        else:
            self._queue: collections.deque[RouterRequest] = \
                collections.deque()
        self.preempt_every = max(1, preempt_every)
        self._last_preempt_tick = -10**9
        self._retiring: set[int] = set()
        self._first_token_t: dict[int, float] = {}
        self._last_signal_counts = (0, 0)
        self._assigned: list[dict[int, RouterRequest]] = [
            {} for _ in self._replicas]
        self._status = [HEALTHY for _ in self._replicas]
        self._last_progress = [None for _ in self._replicas]
        self._last_progress_t = [time.perf_counter()
                                 for _ in self._replicas]
        self._stale = [0 for _ in self._replicas]
        self._clean_probes = [0 for _ in self._replicas]
        self._health: list[dict] = [r.health() for r in self._replicas]
        self._placements = [0 for _ in self._replicas]
        self._ticks = 0
        self._draining = False
        # per-replica draft identity after a hot-swap (ISSUE 16):
        # {index: {"draft_hash", "draft_swaps"}} — survives reset_stats
        # (identity is state, not a counter)
        self._draft_info: dict[int, dict] = {}
        self._recovering: list[dict] = []
        self._occ_sum = [0.0 for _ in self._replicas]
        self._occ_n = [0 for _ in self._replicas]
        for r in self._replicas:
            self._wire_hooks(r)
        self.reset_stats()

    # ------------------------------------------------------------------
    # submission + shedding

    def submit(self, prompt, *, max_new_tokens: int,
               sampling: SamplingParams | None = None, stop_ids=None,
               on_token=None, deadline_s: float | None = None,
               tenant: str | None = None, priority: int = 0,
               kv_window: int | None = None,
               kv_sink: int | None = None,
               session_id: str | None = None) -> RouterRequest:
        """Queue one request with the router (dispatch to a replica
        happens inside step(), against fresh health snapshots). Returns
        the durable RouterRequest handle — ``handle.tokens`` is the
        client stream and survives any number of failovers.

        Admission control: when the router queue already holds
        ``max_queue`` requests, the request is REJECTED here —
        ``done=True, finish_reason="shed"``, zero tokens — instead of
        joining an unbounded line. Shedding at submit is the load-
        shedding contract: overload costs the shed request one cheap
        refusal, not every admitted request its latency SLO."""
        from pytorchdistributed_tpu.inference import stop_ids_tuple

        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must have at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if prompt.size + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt_len {prompt.size} + max_new_tokens "
                f"{max_new_tokens} exceeds max_seq_len "
                f"{self.max_seq_len}")
        if kv_window is not None and kv_window < 1:
            raise ValueError(f"kv_window must be >= 1, got {kv_window}")
        if kv_sink is not None and kv_sink < 0:
            raise ValueError(f"kv_sink must be >= 0, got {kv_sink}")
        if priority < 0:
            raise ValueError(f"priority must be >= 0, got {priority}")
        if session_id is not None:
            from pytorchdistributed_tpu.serving.sessions import (
                session_id_ok,
            )

            if not session_id_ok(session_id):
                raise ValueError(
                    f"malformed session_id {session_id!r} (want "
                    f"[A-Za-z0-9][A-Za-z0-9._:-]*, <= 128 chars)")
        rr = RouterRequest(prompt, max_new_tokens,
                           sampling or SamplingParams(),
                           stop_ids_tuple(stop_ids), on_token,
                           deadline_s=deadline_s, tenant=tenant,
                           priority=priority, kv_window=kv_window,
                           kv_sink=kv_sink, session_id=session_id)
        with span("serve/submit", request=rr.id):
            return self._enqueue(rr)

    def _enqueue(self, rr: RouterRequest) -> RouterRequest:
        """submit()'s second half: stamp, count, and queue (or shed, or
        drain) the validated request."""
        rr.submit_time = time.perf_counter()
        if self.trace is not None:
            # mint the request's fleet-wide trace identity here — the
            # single origin every later emitter (admission, engines on
            # any replica, the handoff wire) parents to
            rr.trace = self.trace.new_trace()
            rr._trace_enq_t = rr.submit_time
        self._stats["submitted"] += 1
        self._tenant_stats(rr.tenant)["submitted"] += 1
        if self._draining:
            self._finish(rr, "drained")
            return rr
        if (self.max_queue is not None
                and len(self._queue) >= self.max_queue):
            # last look before refusing: place whatever the replicas
            # can already hold, so the bound sheds on CAPACITY, not on
            # how recently the caller interleaved a step()
            self._dispatch()
        if self._admission is not None:
            # weighted shedding: offer() admits, rate-refuses, or —
            # when the global bound is hit — picks the victim from the
            # tenant FURTHEST OVER its weight share (the arrival
            # itself when its own tenant is the worst offender). A
            # compliant tenant's requests are untouchable.
            victim = self._queue.offer(rr)
            if victim is not None:
                self._stats["shed_requests"] += 1
                self._event("shed", request=victim.id,
                            tenant=victim.tenant,
                            queued=len(self._queue))
                self._finish(victim, "shed")
            return rr
        if (self.max_queue is not None
                and len(self._queue) >= self.max_queue):
            self._stats["shed_requests"] += 1
            self._event("shed", request=rr.id, tenant=rr.tenant,
                        queued=len(self._queue))
            self._finish(rr, "shed")
            return rr
        self._queue.append(rr)
        return rr

    # ------------------------------------------------------------------
    # the router loop

    def step(self) -> dict:
        """One router iteration:

          1. consult the fault injector (chaos schedule) per replica;
          2. refresh health snapshots; run the hang watchdog and the
             sick-probe/quarantine/rejoin state machine;
          3. dispatch queued requests to the least-loaded replicas
             with room;
          4. step every healthy replica one engine step (a crash here
             is caught and becomes a failover);
          5. reap finished requests and expired router-queue deadlines.
        """
        if self._draining:
            self.drain()
            return self._step_stats(0)
        self._ticks += 1
        with span("serve/router_step", step=self._ticks):
            with span("serve/router_health"):
                # 1. chaos schedule
                self._inject_faults()
                # 2. health + watchdog + quarantine machine
                self._check_health()
                # 2b. respawn DEAD replicas with budget left (ISSUE 10)
                # — recovered capacity rejoins through the quarantine
                # machine
                self._maybe_respawn()
            with span("serve/router_dispatch"):
                # 3. dispatch
                dispatched = self._dispatch()
                # 3b. admission-pressure preemption: a starved compliant
                # tenant at the head of a saturated fleet may evict an
                # over-budget tenant's newest stream (losslessly —
                # preempt-requeue)
                self._maybe_preempt()
            # 4. step replicas — DRAINING ones too: their resident
            # streams must finish before the tombstone
            for r in self._replicas:
                if self._status[r.index] not in (HEALTHY, DRAINING):
                    continue
                try:
                    with span("serve/replica_step", replica=r.index):
                        r.step()
                except ReplicaCrashed:
                    self._declare_dead(r, "crashed")
            with span("serve/router_reap"):
                # 4a. persist replica-demoted sessions (ISSUE 18)
                self._persist_demoted_sessions()
                # 4b. sweep parked prefill-role admissions onto
                # decode-capable replicas over the KV stream (ISSUE 12)
                self._handoffs()
                # 5. reap
                self._reap()
                self._expire_queued_deadlines()
                # 5b. finalize scale-downs: a DRAINING replica with
                # nothing resident closes and becomes a tombstone
                self._finalize_removals()
                if self._ticks % self.sample_every == 0:
                    self._sample_replicas()
                self._feed_signals()
        return self._step_stats(dispatched)

    def _inject_faults(self) -> None:
        """Consult the chaos schedule for every live replica. One-shot
        tick specs: in-process replicas only (subprocess workers fire
        the injector against their own RANK — consulting it here too
        would consume the one-shot marker and log an injection that
        never happened). RATE-BASED schedules (ChaosSchedule) are
        consulted for EVERY replica — their seeded decisions live
        router-side, and the router applies them (kill/SIGSTOP/wire op)
        playing the cluster — with ``rate_only`` guarding subprocess
        one-shots."""
        if self._faults is None:
            return
        rate_based = getattr(self._faults, "rate_based", False)
        for r in self._replicas:
            if self._status[r.index] in (DEAD, REMOVED):
                continue
            in_worker = getattr(r, "faults_in_worker", False)
            if in_worker and not rate_based:
                continue
            kind = (self._faults.on_serving_tick(
                        self._ticks, r.index, rate_only=True)
                    if in_worker else
                    self._faults.on_serving_tick(self._ticks, r.index))
            if kind:
                spec = getattr(self._faults, "last_fired", None)
                self._stats["faults_injected"] += 1
                self._event("fault_injected", replica=r.index,
                            fault=kind,
                            spec=(spec.describe() if spec else kind))
                try:
                    r.apply_fault(kind, ms=(spec.ms if spec else 100.0))
                except (ReplicaCrashed, TimeoutError):
                    self._declare_dead(r, "crashed")

    def _persist_demoted_sessions(self) -> None:
        """Move replica-demoted sessions into the store tiers (ISSUE 18)
        — the engine's HBM budget pushed them out; the store's DRAM/disk
        tiers keep them reattachable."""
        if self.session_store is None:
            return
        for r in self._replicas:
            if self._status[r.index] not in (HEALTHY, DRAINING):
                continue
            try:
                demoted = r.take_demoted_sessions()
            except (ReplicaCrashed, TimeoutError):
                self._declare_dead(r, "crashed")
                continue
            for sid, tenant, payload in demoted:
                self.session_store.put(sid, payload, tenant=tenant)
                self._session_index.discard(sid)
                self._stats["session_demotes"] += 1

    def _sample_replicas(self) -> None:
        """One per-replica health/load row into the router telemetry."""
        for r in self._replicas:
            if self._status[r.index] == REMOVED:
                continue
            h = self._health[r.index]
            self.telemetry.replica(
                tick=self._ticks, replica=r.index,
                status=self._status[r.index],
                role=self._roles[r.index],
                active=h.get("active", 0), queued=h.get("queued", 0),
                parked=h.get("parked", 0),
                occupancy=round(h.get("occupancy", 0.0), 4),
                progress=h.get("progress", -1))

    def _feed_signals(self) -> None:
        """One sample per autoscaler signal per tick, into the
        telemetry rings — queue depth, mean healthy occupancy, fleet
        TTFT EMA, per-tick submitted/shed deltas (windowed shed RATE is
        computed ring-side), prefill backlog, healthy count."""
        healthy = [self._health[i] for i, s in enumerate(self._status)
                   if s == HEALTHY]
        occ = (sum(h.get("occupancy", 0.0) for h in healthy)
               / len(healthy)) if healthy else None
        emas = [h.get("ttft_ema_s") for h in healthy]
        emas = [e for e in emas if e]
        backlog = len(self._queue) + sum(
            h.get("prefilling", 0) + h.get("parked", 0) for h in healthy)
        sub, shed = (self._stats["submitted"],
                     self._stats["shed_requests"])
        dsub = sub - self._last_signal_counts[0]
        dshed = shed - self._last_signal_counts[1]
        self._last_signal_counts = (sub, shed)
        self.telemetry.signal(
            queue_depth=len(self._queue), occupancy=occ,
            ttft_ema_s=(sum(emas) / len(emas)) if emas else None,
            submitted=dsub, shed=dshed, prefill_backlog=backlog,
            healthy=sum(s == HEALTHY for s in self._status),
            in_flight=self.in_flight)

    def _step_stats(self, dispatched: int) -> dict:
        return {"tick": self._ticks, "dispatched": dispatched,
                "queued": len(self._queue),
                "in_flight": sum(len(a) for a in self._assigned),
                "healthy": sum(s == HEALTHY for s in self._status)}

    # -- health machine ------------------------------------------------

    def _check_health(self) -> None:
        for r in self._replicas:
            i = r.index
            if self._status[i] in (DEAD, REMOVED):
                continue
            try:
                h = r.health()
            except ReplicaCrashed:
                self._declare_dead(r, "crashed")
                continue
            self._health[i] = h
            if "prefix_frontier" in h:
                self._prefix_index.update(i, h["prefix_frontier"])
            if "session_frontier" in h:
                self._session_index.update(i, h["session_frontier"])
            if not h.get("alive", True):
                self._declare_dead(r, "crashed")
                continue
            # wire protocol fault (ISSUE 19): an unparseable line set
            # the replica's flag in _try_recv — classify it as SICK
            # (quarantine → clean-probe streak → canary rejoin, the
            # same path a NaN'd replica walks), never an uncaught raise
            if getattr(r, "_protocol_fault", False):
                r._protocol_fault = False
                self._stats["wire_faults"] += 1
                self._event("wire_fault_detected", replica=i,
                            bad_lines=getattr(r, "protocol_faults", 0))
                if self._status[i] == HEALTHY:
                    self._quarantine(r)
                    continue
                # already quarantined/draining: the torn line resets
                # the streak — rejoin must be earned on a clean wire
                self._clean_probes[i] = 0
            # DRAINING replicas keep the watchdog: a scale-down target
            # that hangs mid-drain must still be shot (its streams fail
            # over) instead of stranding them behind a tombstone-to-be
            if self._status[i] in (HEALTHY, DRAINING):
                self._occ_sum[i] += h.get("occupancy", 0.0)
                self._occ_n[i] += 1
                # hang watchdog: work assigned + watermark frozen for
                # hang_ticks ticks AND (async replicas) longer than the
                # replica's wall-clock grace — a fast-spinning idle
                # router must not out-run a healthy subprocess worker's
                # response latency
                now = time.perf_counter()
                prog = h.get("progress", -1)
                # a stream parked for KV handoff (or queued behind
                # parked slots) is waiting on a decode slot, not on this
                # replica's compiled step — only work the engine has
                # actually admitted freezes the watermark, or a
                # saturated decode fleet would get every prefill replica
                # shot as "hung" while its exports queue
                working = (h.get("active", 0)
                           + h.get("prefilling", 0)) > 0
                if (self._assigned[i] and working
                        and prog == self._last_progress[i]):
                    self._stale[i] += 1
                else:
                    self._stale[i] = 0
                    self._last_progress_t[i] = now
                self._last_progress[i] = prog
                if (self._stale[i] >= self.hang_ticks
                        and now - self._last_progress_t[i]
                        >= getattr(r, "hang_grace_s", 0.0)):
                    self._declare_dead(r, "hung")
                    continue
                # periodic sick probe (HEALTHY only: a DRAINING replica
                # is leaving regardless — quarantining it would erase
                # the scale-down marker, and its streams are minutes
                # from done; crash/hang detection still covers it)
                if (self._status[i] == HEALTHY
                        and self._ticks % self.health_every == 0):
                    try:
                        ok = r.probe()
                    except ReplicaCrashed:
                        self._declare_dead(r, "crashed")
                        continue
                    if not ok:
                        self._quarantine(r)
            elif self._status[i] == QUARANTINED:
                # a respawned worker still WARMING past its startup
                # bound is wedged (bad node, poisoned restore): the
                # sync warmup() path had wait_response(600) — the async
                # path must enforce the same bound, or the slot parks
                # forever with respawn budget unspent
                if (getattr(r, "_warming", False)
                        and 0 < self._warming_deadline[i]
                        < time.perf_counter()):
                    self._declare_dead(r, "hung")
                    continue
                try:
                    ok = r.probe(exclusive=True)
                except ReplicaCrashed:
                    self._declare_dead(r, "crashed")
                    continue
                self._clean_probes[i] = self._clean_probes[i] + 1 if ok \
                    else 0
                if self._clean_probes[i] >= self.rejoin_after:
                    self._rejoin(r)

    def _declare_dead(self, r, why: str) -> None:
        if self._status[r.index] == DEAD:
            return
        self._status[r.index] = DEAD
        self._prefix_index.remove(r.index)
        # resident sessions died with the replica: forget the ownership
        # claims so reattaches fall through to the store tiers
        self._session_index.remove(r.index)
        # a respawn reboots from the SPEC's draft (if any) — the swapped
        # identity died with the process
        self._draft_info.pop(r.index, None)
        self._stats["replicas_lost"] += 1
        if why == "hung":
            self._stats["hangs_detected"] += 1
        if self.respawn_budget:
            # arm the respawn gate: attempt k waits the policy's k-th
            # exponential delay, so a crash-looping replica burns its
            # budget slowly instead of spawn-storming
            self._respawn_eligible[r.index] = (
                time.perf_counter()
                + self.respawn_policy.delay(1 + self._respawns[r.index],
                                            self._rng))
        self._event("replica_dead", replica=r.index, why=why,
                    stale_ticks=self._stale[r.index])
        self._failover(r, why)

    # -- respawn (ISSUE 10) --------------------------------------------

    def _maybe_respawn(self) -> None:
        """Relaunch DEAD replicas that still have respawn budget and
        whose backoff gate has opened. A fresh replica enters
        QUARANTINED, not HEALTHY: it must earn its way back through the
        same clean-probe streak + warmup canary a NaN-recovered replica
        does — a respawn that comes up broken (corrupt checkpoint, bad
        node) costs probes, never traffic."""
        if not self.respawn_budget or self._draining:
            return
        now = time.perf_counter()
        for i, r in enumerate(self._replicas):
            if (self._status[i] != DEAD
                    or i in self._retiring  # scale-down target: stay down
                    or self._respawns[i] >= self.respawn_budget
                    or now < self._respawn_eligible[i]):
                continue
            self._respawns[i] += 1
            attempt = self._respawns[i]
            # arm the NEXT attempt's gate up front — a failed spawn
            # below must not retry on the very next tick
            self._respawn_eligible[i] = (
                now + self.respawn_policy.delay(1 + attempt, self._rng))
            self._dispose_corpse(r)
            fresh = None
            try:
                fresh = self._build_replacement(r)
                if isinstance(fresh, SubprocessReplica):
                    # NON-blocking: the replacement's startup (jax
                    # import + restore + warmup) runs while the router
                    # keeps ticking the healthy replicas; probe()
                    # reports un-ready until the warmup reply lands,
                    # so the quarantine machine holds it parked —
                    # bounded by respawn_warmup_s (checked in
                    # _check_health), or a wedged startup would park
                    # the slot forever
                    fresh.warmup_async(self.warmup_lens)
                    self._warming_deadline[i] = (
                        time.perf_counter() + self.respawn_warmup_s)
                else:
                    # in-process engines share the router's thread by
                    # construction; their warmup is the (cached) fast
                    # path and cannot be deferred off-thread
                    fresh.warmup(self.warmup_lens)
            except Exception as e:  # noqa: BLE001 — spawn is best-effort
                if fresh is not None:
                    try:  # a half-spawned worker must not linger
                        fresh.close()
                    except Exception:  # noqa: BLE001
                        pass
                self._stats["respawn_failures"] += 1
                self._event("respawn_failed", replica=i, attempt=attempt,
                            error=f"{type(e).__name__}: {e}"[:200])
                if attempt >= self.respawn_budget:
                    self._event("respawn_exhausted", replica=i,
                                attempts=attempt)
                continue
            self._replicas[i] = fresh
            self._status[i] = QUARANTINED
            self._clean_probes[i] = 0
            self._stale[i] = 0
            self._last_progress[i] = None
            self._last_progress_t[i] = time.perf_counter()
            try:
                self._health[i] = fresh.health()
            except ReplicaCrashed:
                self._declare_dead(fresh, "crashed")
                continue
            self._stats["respawns"] += 1
            self._event("respawn", replica=i, attempt=attempt)

    def _dispose_corpse(self, r) -> None:
        """Tear down a DEAD replica without the graceful-close protocol
        (it is dead — there is nobody to drain) and with a SHORT
        kill_group grace, so reclaiming a wedged corpse costs the tick
        loop ~a second, not the full shutdown escalation."""
        try:
            if isinstance(r, SubprocessReplica):
                from pytorchdistributed_tpu.run import kill_group

                kill_group([r.proc], grace=1.0)
                r.alive = False
                for pipe in (r.proc.stdin, r.proc.stdout):
                    try:
                        pipe.close()
                    except OSError:
                        pass
            else:
                r.close()
        except Exception:  # noqa: BLE001 — the corpse can't block us
            pass

    def _build_replacement(self, r):
        if isinstance(r, SubprocessReplica):
            fresh = SubprocessReplica(
                r.index, self._worker_specs[r.index],
                world_size=len(self._replicas),
                heartbeat_dir=self._hb_dir,
                master_port=self._worker_port,
                env=self._worker_env)
            self._wire_hooks(fresh)
            return fresh
        if isinstance(r, InProcessReplica):
            return InProcessReplica(r.index, r._factory,
                                    warmup_lens=r.warmup_lens)
        raise TypeError(f"cannot respawn replica type {type(r).__name__}")

    def _wire_hooks(self, r) -> None:
        """Install the wire-fault surface on a subprocess replica
        (fresh fleet, respawn and scale-up alike): the ChaosSchedule
        mangler when one is active, and the event sink that lands
        wire_fault/wire_slow/wire_retry/wire_timeout rows in router
        telemetry with the replica index stamped."""
        if not isinstance(r, SubprocessReplica):
            return
        if (self._faults is not None
                and hasattr(self._faults, "mangle_recv")):
            r.wire_chaos = self._faults
        r.on_wire_event = (
            lambda ev, _i=r.index, **row: self._event(
                ev, replica=_i, **row))

    def _fleet_unrecoverable(self) -> bool:
        """All replicas DEAD *and* no respawn can ever bring one back —
        the only state where waiting on the router is hopeless."""
        if any(s not in (DEAD, REMOVED) for s in self._status):
            return False
        if all(s == REMOVED for s in self._status):
            return True   # fully scaled away: nothing respawns a tombstone
        if not self.respawn_budget:
            return True
        return all(n >= self.respawn_budget or i in self._retiring
                   for i, n in enumerate(self._respawns)
                   if self._status[i] == DEAD)

    def _quarantine(self, r) -> None:
        """Sick (params non-finite): fail its streams over NOW — every
        token it would emit is garbage — then park it out of rotation,
        probing for recovery."""
        self._status[r.index] = QUARANTINED
        self._prefix_index.remove(r.index)
        # KV written under non-finite params is poison: drop ownership
        # AND discard any pending demoted-session payloads instead of
        # persisting them — a reattach must re-prefill, never resume
        # from a sick replica's blocks
        self._session_index.remove(r.index)
        self._clean_probes[r.index] = 0
        self._stats["quarantines"] += 1
        self._event("quarantine", replica=r.index)
        self._failover(r, "sick")
        try:
            r.quarantine_reset()
            r.take_demoted_sessions()
        except (ReplicaCrashed, TimeoutError):
            self._declare_dead(r, "crashed")

    def _rejoin(self, r) -> None:
        """Probe streak clean → warmup re-admission: run one canary
        request end-to-end on the replica (re-exercising prefill +
        tick on the repaired weights) before real traffic returns.
        In-process the canary is synchronous and cheap (the programs
        are already compiled — a rejoin costs zero recompiles)."""
        if isinstance(r, InProcessReplica):
            try:
                n = min(self.warmup_lens[0] if self.warmup_lens else 8,
                        self.max_seq_len - 2)
                canary = r.engine.submit(np.zeros(n, np.int32),
                                         max_new_tokens=2)
                r.engine.run_until_idle()
                if not canary.done or not r.probe():
                    self._clean_probes[r.index] = 0
                    return  # not actually ready — keep quarantined
            except ReplicaCrashed:
                self._declare_dead(r, "crashed")
                return
        self._status[r.index] = HEALTHY
        self._stale[r.index] = 0
        self._last_progress[r.index] = None
        self._last_progress_t[r.index] = time.perf_counter()
        self._stats["rejoins"] += 1
        self._event("rejoin", replica=r.index)

    # -- elastic scaling (ISSUE 15) ------------------------------------

    def add_replica(self, role: str = ROLE_BOTH) -> int:
        """Grow the fleet by one replica at a NEW index (tombstoned
        indices are never reused — the per-replica parallel lists are
        append-only, so every replica's counters and occupancy history
        survive into the summary).

        In-process replicas warm synchronously and join HEALTHY at
        once: they share the fleet's jit cache, so warmup is a cache
        hit — ZERO fresh compiles (the warm-join property the
        flash-crowd test pins). Subprocess replicas launch under the
        same spec/env contract as an ISSUE-10 respawn — checkpoint
        restore, JAX's persistent compilation cache — warm
        ASYNCHRONOUSLY and join through the quarantine -> clean-probe gauntlet,
        exactly like a recovered crash."""
        if role not in ROLES:
            raise ValueError(
                f"unknown role {role!r} (want one of {ROLES})")
        i = len(self._replicas)
        if self._worker_specs is not None:
            spec = self._base_specs[i % len(self._base_specs)]
            self._worker_specs.append(spec)
            fresh = SubprocessReplica(
                i, spec, world_size=i + 1, heartbeat_dir=self._hb_dir,
                master_port=self._worker_port,
                env=self._worker_env)
            self._wire_hooks(fresh)
        else:
            fresh = InProcessReplica(i, self._factory_fn(i),
                                     warmup_lens=self.warmup_lens)
        self._replicas.append(fresh)
        self._roles.append(role)
        self._assigned.append({})
        self._status.append(QUARANTINED)
        self._last_progress.append(None)
        self._last_progress_t.append(time.perf_counter())
        self._stale.append(0)
        self._clean_probes.append(0)
        self._health.append({"alive": True, "progress": -1})
        self._placements.append(0)
        self._respawns.append(0)
        self._respawn_eligible.append(0.0)
        self._warming_deadline.append(0.0)
        self._occ_sum.append(0.0)
        self._occ_n.append(0)
        self._disagg = any(x != ROLE_BOTH for x in self._roles)
        if isinstance(fresh, SubprocessReplica):
            fresh.warmup_async(self.warmup_lens)
            self._warming_deadline[i] = (time.perf_counter()
                                         + self.respawn_warmup_s)
        else:
            fresh.warmup(self.warmup_lens)
            self._status[i] = HEALTHY
            self._health[i] = fresh.health()
        self._stats["scale_ups"] += 1
        self._event("scale_up", replica=i, role=role,
                    mode=("async" if isinstance(fresh, SubprocessReplica)
                          else "warm"))
        return i

    def remove_replica(self, index: int | None = None,
                       role: str | None = None) -> int | None:
        """Begin a graceful scale-down: pick the least-loaded HEALTHY
        replica (optionally a specific ``index``, optionally matching
        ``role``), mark it DRAINING — it keeps stepping its resident
        streams (and handing off parked prefills) but admits nothing
        new, then closes into a REMOVED tombstone once empty. Returns
        the chosen index, or None when nothing can be spared: never
        the last healthy replica, and in a disaggregated fleet never
        the last healthy prefill- or decode-capable one."""
        healthy = [i for i, s in enumerate(self._status)
                   if s == HEALTHY]

        def sparable(i: int) -> bool:
            rest = [j for j in healthy if j != i]
            if not rest:
                return False
            if self._disagg:
                for caps in ((ROLE_DECODE, ROLE_BOTH),
                             (ROLE_PREFILL, ROLE_BOTH)):
                    if (self._roles[i] in caps
                            and not any(self._roles[j] in caps
                                        for j in rest)):
                        return False
            return True

        cands = [i for i in healthy
                 if (index is None or i == index)
                 and (role is None or self._roles[i] == role)
                 and sparable(i)]
        if not cands:
            return None
        # least resident work first; highest index breaks ties (LIFO
        # scale-down pairs with append-only scale-up)
        i = min(cands, key=lambda j: (
            len(self._assigned[j]),
            self._health[j].get("occupancy", 0.0), -j))
        self._status[i] = DRAINING
        self._retiring.add(i)
        self._prefix_index.remove(i)
        self._stats["scale_downs"] += 1
        self._event("scale_down", replica=i, role=self._roles[i],
                    resident=len(self._assigned[i]))
        return i

    def _persist_replica_sessions(self, r) -> None:
        """Demote-and-persist a replica's resident sessions before it
        goes away (close / scale-down tombstone): drain the engine —
        which pushes every parked session into its demote queue — then
        sweep the queue into the store tiers. Restart survival for the
        warm tier; best-effort (a wedged replica just loses its HBM
        tier and reattaches re-prefill)."""
        if self.session_store is None:
            return
        try:
            r.drain()
            demoted = r.take_demoted_sessions()
        except (ReplicaCrashed, TimeoutError):
            return
        for sid, tenant, payload in demoted:
            self.session_store.put(sid, payload, tenant=tenant)
            self._session_index.discard(sid)
            self._stats["session_demotes"] += 1

    def _finalize_removals(self) -> None:
        for i, s in enumerate(self._status):
            if s != DRAINING or self._assigned[i]:
                continue
            self._persist_replica_sessions(self._replicas[i])
            try:
                self._replicas[i].close()
            except Exception:  # noqa: BLE001 — the tombstone wins
                pass
            self._status[i] = REMOVED
            self._event("replica_removed", replica=i)

    def pool_state(self) -> dict[str, dict]:
        """Aggregate per-pool capacity view (the autoscaler's scaling
        input): one ``"fleet"`` pool colocated; separate ``"prefill"``
        and ``"decode"`` pools when disaggregated (ROLE_BOTH counts
        decode — it receives handoffs)."""
        def agg(idxs):
            idxs = list(idxs)
            healthy = [i for i in idxs if self._status[i] == HEALTHY]
            hs = [self._health[i] for i in healthy]
            return {
                "replicas": len(idxs),
                "healthy": len(healthy),
                "draining": sum(self._status[i] == DRAINING
                                for i in idxs),
                "quarantined": sum(self._status[i] == QUARANTINED
                                   for i in idxs),
                "dead": sum(self._status[i] == DEAD for i in idxs),
                "removed": sum(self._status[i] == REMOVED
                               for i in idxs),
                "occupancy": (sum(h.get("occupancy", 0.0) for h in hs)
                              / len(hs)) if hs else None,
                "free_slots": sum(h.get("free_slots", 0) for h in hs),
                "queued": sum(h.get("queued", 0) for h in hs),
                "prefilling": sum(h.get("prefilling", 0) for h in hs),
                "parked": sum(h.get("parked", 0) for h in hs),
            }

        if not self._disagg:
            return {"fleet": agg(range(len(self._replicas)))}
        return {
            "prefill": agg(i for i, ro in enumerate(self._roles)
                           if ro == ROLE_PREFILL),
            "decode": agg(i for i, ro in enumerate(self._roles)
                          if ro in (ROLE_DECODE, ROLE_BOTH)),
        }

    # -- admission-pressure preemption (ISSUE 15) ----------------------

    def _maybe_preempt(self) -> None:
        """When a COMPLIANT tenant's request heads the queue and the
        fleet is saturated, evict the newest active stream of the
        tenant furthest over its weight share — losslessly, over the
        engine's preempt-requeue path (the evicted stream resumes from
        its delivered tokens once capacity frees). Rate-limited to one
        eviction per ``preempt_every`` ticks: preemption pays a
        re-prefill, so it must relieve starvation, not thrash."""
        if self._admission is None or self._draining:
            return
        if self._ticks - self._last_preempt_tick < self.preempt_every:
            return
        starved = self._queue.starved_head()
        if starved is None:
            return
        # only under saturation: with room anywhere, plain dispatch
        # serves the starved head next tick
        for i, s in enumerate(self._status):
            if s != HEALTHY:
                continue
            h = self._health[i]
            load = (h.get("active", 0) + h.get("queued", 0)
                    + h.get("prefilling", 0) + h.get("parked", 0))
            if load < h.get("num_slots", 1) + self.max_pending:
                return
        over = self._queue.overages()
        best = None
        for i, s in enumerate(self._status):
            if s != HEALTHY:
                continue
            for rr in self._assigned[i].values():
                o = over.get(rr.tenant, 0.0)
                if o <= 0 or rr.tenant == starved.tenant:
                    continue
                key = (o, rr.id)   # worst overage; newest stream
                if best is None or key > best[0]:
                    best = (key, rr, i)
        if best is None:
            return
        _, rr, idx = best
        try:
            ok = self._replicas[idx].preempt(rr)
        except WireFault:
            # the wire mangled the preempt reply: the stream is still
            # resident and live — skip this round; the protocol-fault
            # sweep decides the replica's fate
            return
        except (ReplicaCrashed, TimeoutError):
            self._declare_dead(self._replicas[idx], "crashed")
            return
        if ok:
            self._last_preempt_tick = self._ticks
            self._stats["preemptions"] += 1
            self._event("preempt", request=rr.id, tenant=rr.tenant,
                        replica=idx, for_tenant=starved.tenant,
                        tokens_so_far=len(rr.tokens))

    # -- failover ------------------------------------------------------

    def _failover(self, r, why: str) -> None:
        """Redispatch every in-flight request of a lost replica. The
        RouterRequest carries prompt + sampling + seed + delivered
        tokens, so survivors resume the stream losslessly
        (submit(generated=...)); a retry budget caps how many deaths a
        single request may surf, and the backoff gate keeps a flapping
        fleet from a redispatch storm."""
        victims = list(self._assigned[r.index].values())
        self._assigned[r.index].clear()
        if not victims:
            self._stats["failovers"] += 1
            return
        now = time.perf_counter()
        self._stats["failovers"] += 1
        pending = set()
        for rr in reversed(victims):  # appendleft keeps arrival order
            if rr._handle is not None and getattr(rr._handle, "done",
                                                  False):
                # finished on the replica in its final moments, not yet
                # reaped — deliverable as-is, no redispatch needed
                self._finish(rr, rr._handle.finish_reason)
                continue
            rr._handle = None
            rr._replica = None
            rr.retries += 1
            if rr.retries > self.max_retries:
                self._event("retries_exhausted", request=rr.id,
                            retries=rr.retries)
                self._finish(rr, "failed")
                continue
            delay = self.retry_policy.delay(rr.retries, self._rng)
            rr._eligible_at = now + delay
            self._queue.appendleft(rr)
            pending.add(rr.id)
            self._stats["redispatched_requests"] += 1
            self._event("redispatch", request=rr.id, from_replica=r.index,
                        why=why, retries=rr.retries,
                        delay_ms=round(delay * 1e3, 3),
                        tokens_so_far=len(rr.tokens))
            if self.trace is not None and rr.trace is not None:
                # marker span: the failover edge itself; queue
                # residency restarts here, so the NEXT queue span
                # (and the backoff gap, as stall) attribute correctly
                self.trace.span(rr.trace, "redispatch", now, now,
                                from_replica=r.index, why=why,
                                retries=rr.retries)
                rr._trace_enq_t = now
        if pending:
            self._recovering.append(
                {"start": self._ticks, "start_t": now, "pending": pending})

    # -- dispatch ------------------------------------------------------

    def _replica_score(self, h: dict, mean_ttft: float | None) -> float:
        """Lower = less loaded. Occupancy and queue depth dominate;
        pool pressure breaks slot ties (a paged replica about to
        preempt is a worse home than one with headroom); the TTFT EMA
        nudges traffic away from a replica whose admissions have been
        slow (relative to the fleet, so the signal is scale-free)."""
        ns = max(1, h.get("num_slots", 1))
        score = (h.get("occupancy", 0.0)
                 + (h.get("queued", 0) + h.get("prefilling", 0)) / ns
                 + 0.5 * (1.0 - h.get("pool_free_frac", 1.0)))
        ema = h.get("ttft_ema_s")
        if ema is not None and mean_ttft:
            score += 0.25 * min(ema / mean_ttft, 2.0)
        return score

    def _prefix_chain(self, rr: RouterRequest) -> list[str]:
        """The request's prompt as a chained block-hash list, computed
        once and cached on the RouterRequest. Empty when no paged
        replica has published a block size yet (dense fleet, or first
        ticks before health snapshots arrive)."""
        chain = getattr(rr, "_hash_chain", None)
        if chain is not None:
            return chain
        bs = 0
        for h in self._health:
            if h.get("block_size"):
                bs = int(h["block_size"])
                break
        if not bs:
            return []   # not cached: block_size may appear next tick
        chain = block_hashes(np.asarray(rr.prompt), bs)
        rr._hash_chain = chain
        return chain

    def _maybe_ship_prefix(self, rr: RouterRequest, chain: list[str],
                           best) -> None:
        """Fleet-wide prefix reuse: if another healthy replica holds a
        deeper cached match for this prompt than the chosen target, ship
        the matched blocks over the KV stream so the prefix is prefilled
        once per fleet, not once per replica. Best-effort — any failure
        just means the target prefills locally."""
        eligible = {r.index for r in self._replicas
                    if self._status[r.index] == HEALTHY}
        owner, depth = self._prefix_index.best_match(chain,
                                                     eligible=eligible)
        if (owner is None or owner == best.index or depth < 1
                or self._prefix_index.match_depth(best.index,
                                                  chain) >= depth):
            return
        try:
            payload = self._replicas[owner].export_prefix(
                np.asarray(rr.prompt))
            if payload is None:
                return
            adopted = best.import_prefix(payload)
        except (ReplicaCrashed, TimeoutError):
            return  # health machinery will notice on its own
        if adopted:
            self._stats["prefix_ships"] += 1
            self._stats["kv_stream_bytes"] += payload.nbytes
            # optimistic: the target now holds these blocks — steer
            # follow-on siblings there before its next health refresh
            self._prefix_index.add(best.index, chain[:depth])
            self._event("prefix_ship", request=rr.id, owner=owner,
                        target=best.index, blocks=adopted, depth=depth)

    def _prepare_session(self, rr: RouterRequest, r) -> None:
        """Reattach plumbing before placement (ISSUE 18): make the
        session's KV resident on the TARGET replica so the submit rides
        an ordinary prefix hit. Tier order — already home (the index
        steered us to the owner: the engine adopts internally), pull
        from the owning replica over the wire, then the store's
        host-DRAM/disk tiers. Every decline falls through; when the
        session was KNOWN somewhere and still ends up re-prefilling,
        that's the LOUD lossless fallback (session_fallback event)."""
        sid = rr.session_id
        eligible = [i for i, s in enumerate(self._status)
                    if s in (HEALTHY, DRAINING)]
        owner = self._session_index.owner(sid, eligible)
        if owner == r.index:
            self._stats["session_reattach"]["hbm"] += 1
            self._event("session_reattach", session=sid, tier="hbm",
                        replica=r.index)
            return
        known = owner is not None or (
            self.session_store is not None
            and self.session_store.peek_tier(sid) is not None)
        payload, tier = None, "hbm"
        if owner is not None:
            try:
                payload = self._replicas[owner].export_session(sid)
            except (ReplicaCrashed, TimeoutError):
                payload = None  # health machinery will notice
            # the export popped it (or the owner never had it): either
            # way the claim is stale now
            self._session_index.discard(sid)
        if payload is None and self.session_store is not None:
            got = self.session_store.get(sid)
            if got is not None:
                payload, tier = got
        if payload is not None:
            try:
                seeded = r.seed_session(payload)
            except (ReplicaCrashed, TimeoutError):
                seeded = 0
            if seeded > 0:
                self._stats["session_reattach"][tier] += 1
                if tier == "hbm":
                    # crossed the wire replica→replica
                    self._stats["session_ships"] += 1
                    self._stats["kv_stream_bytes"] += payload.nbytes
                self._event("session_reattach", session=sid, tier=tier,
                            replica=r.index, owner=owner, tokens=seeded)
                return
            if tier == "hbm" and self.session_store is not None:
                # seed declined but the payload was already popped off
                # the owner — park it in the store rather than lose it
                self.session_store.put(sid, payload, tenant=rr.tenant)
        if known:
            self._stats["session_fallbacks"] += 1
            self._event("session_fallback", session=sid,
                        replica=r.index, owner=owner,
                        tier=(tier if payload is not None else None))

    def _dispatch(self) -> int:
        healthy = [r for r in self._replicas
                   if self._status[r.index] == HEALTHY]
        if not healthy or not self._queue:
            return 0
        # disaggregated fleet: new admissions go to prefill-capable
        # replicas (role prefill/both); if none survive, availability
        # beats role purity and any healthy replica may admit
        cands = healthy
        if self._disagg:
            pref = [r for r in healthy
                    if self._roles[r.index] in (ROLE_PREFILL, ROLE_BOTH)]
            cands = pref or healthy
        emas = [self._health[r.index].get("ttft_ema_s") for r in cands]
        emas = [e for e in emas if e]
        mean_ttft = sum(emas) / len(emas) if emas else None
        now = time.perf_counter()
        dispatched = 0
        deferred: list[RouterRequest] = []
        while self._queue:
            rr = self._queue.popleft()
            if rr.done:
                continue
            if rr._eligible_at > now:   # redispatch backoff
                deferred.append(rr)
                continue
            if rr.deadline_s is not None:
                remaining = rr.deadline_s - (now - rr.submit_time)
                if remaining <= 0:
                    self._finish(rr, "deadline")
                    continue
            # room = the replica can hold it without unbounded queueing;
            # ties break toward the replica with fewer lifetime
            # placements (deterministic round-robin under light load —
            # a pure index tie-break would starve the higher indices).
            # A published prefix match dominates the key: landing on the
            # replica that already holds the blocks skips whole prefill
            # chunks, which is worth more than any load delta
            chain = self._prefix_chain(rr)
            # session affinity dominates even prefix depth: the owner
            # replica holds the WHOLE conversation's blocks resident —
            # landing there costs zero wire bytes and zero re-prefill
            sowner = (self._session_index.owner(
                rr.session_id, [r.index for r in cands])
                if rr.session_id is not None else None)
            best, best_key = None, None
            for r in cands:
                h = self._health[r.index]
                load = (h.get("active", 0) + h.get("queued", 0)
                        + h.get("prefilling", 0) + h.get("parked", 0))
                if load >= h.get("num_slots", 1) + self.max_pending:
                    continue
                depth = (self._prefix_index.match_depth(r.index, chain)
                         if chain else 0)
                key = (0 if sowner == r.index else 1,
                       -depth, self._replica_score(h, mean_ttft),
                       self._placements[r.index], r.index)
                if best_key is None or key < best_key:
                    best, best_key = r, key
            if best is None:
                deferred.append(rr)   # every replica full: wait
                break
            if chain and not rr.tokens:
                self._maybe_ship_prefix(rr, chain, best)
            with span("serve/dispatch", request=rr.id,
                      replica=best.index) as placing:
                placed = self._place(rr, best)
                # in process the handle is the engine's Request: its id
                # is what the engine's own spans call `request`
                eid = getattr(rr._handle, "id", None)
                if placed and eid is not None:
                    placing.note(engine_request=eid)
            if not placed:
                # the pick died at placement (request was requeued);
                # stop this pass — the next tick re-dispatches against
                # refreshed health, never against this stale snapshot
                break
            dispatched += 1
        # untouched tail keeps FIFO order behind the deferred heads
        for rr in reversed(deferred):
            self._queue.appendleft(rr)
        return dispatched

    def _place(self, rr: RouterRequest, r) -> bool:
        remaining = None
        if rr.deadline_s is not None:
            remaining = max(
                0.001,
                rr.deadline_s - (time.perf_counter() - rr.submit_time))

        # first arg is the engine Request (in-process) or the rid
        # (subprocess) — either way the RouterRequest closure is the
        # identity that matters
        def cb(_handle, tok, rr=rr, idx=r.index):
            self._on_token(rr, idx, tok)

        # a prefill-role replica parks the stream after its first token
        # for KV handoff — but only while a decode-capable replica is
        # alive to receive it; otherwise it decodes in place (lossy
        # topology never beats a lost stream)
        prefill_only = (
            self._disagg
            and self._roles[r.index] == ROLE_PREFILL
            and bool(self._health[r.index].get("block_size"))
            and any(self._status[x.index] == HEALTHY
                    and self._roles[x.index] in (ROLE_DECODE, ROLE_BOTH)
                    for x in self._replicas))
        # reattach prep (ISSUE 18): fresh turns only — a failover
        # redispatch resumes from its delivered tokens, and a non-paged
        # target (no block_size in health) has no tiers to seed
        if (rr.session_id is not None and not rr.tokens
                and self._health[r.index].get("block_size")):
            self._prepare_session(rr, r)
        try:
            handle = r.submit(rr, generated=rr.tokens or None,
                              deadline_s=remaining, on_token=cb,
                              prefill_only=prefill_only)
        except WireFault:
            # the wire mangled something DURING placement: the replica
            # is suspect, not dead — requeue the request and let the
            # protocol-fault sweep quarantine it (no death sentence
            # for a torn line)
            self._queue.appendleft(rr)
            if self.trace is not None and rr.trace is not None:
                now = time.perf_counter()
                self.trace.span(rr.trace, "redispatch", now, now,
                                from_replica=r.index, why="wire_fault")
                rr._trace_enq_t = now
            return False
        except (ReplicaCrashed, TimeoutError):
            # the pick died (or stopped answering) between health check
            # and placement: requeue the request, let the health
            # machinery take the replica down
            self._queue.appendleft(rr)
            if self.trace is not None and rr.trace is not None:
                now = time.perf_counter()
                self.trace.span(rr.trace, "redispatch", now, now,
                                from_replica=r.index, why="place_crash")
                rr._trace_enq_t = now
            self._declare_dead(r, "crashed")
            return False
        except ValueError as e:
            # the replica REFUSED the request (e.g. a per-request KV
            # override its pool can't honor): terminal — every replica
            # in a homogeneous fleet would refuse it the same way, so
            # fail LOUDLY rather than redispatch-storm
            self._finish(rr, "failed")
            self._event("rejected", request=rr.id, replica=r.index,
                        tenant=rr.tenant, error=str(e)[:200])
            return True
        rr._handle = handle
        rr._replica = r.index
        rr.replicas.append(r.index)
        if rr.session_id is not None:
            # optimistic ownership: the stream parks HERE at finish —
            # steer the next turn before the health refresh catches up
            self._session_index.add(r.index, rr.session_id)
        self._placements[r.index] += 1
        self._assigned[r.index][rr.id] = rr
        # keep this tick's snapshot honest for the next pick
        self._health[r.index]["queued"] = \
            self._health[r.index].get("queued", 0) + 1
        if self.trace is not None and rr.trace is not None:
            # queue = residency start -> WDRR dequeue; admission =
            # dequeue -> the engine accepting the stream. The dequeue
            # stamp comes from AdmissionController.popleft (falls back
            # to now on the plain-deque path)
            now = time.perf_counter()
            t0 = rr._trace_enq_t if rr._trace_enq_t is not None \
                else rr.submit_time
            dq = rr.dequeue_time if rr.dequeue_time is not None else now
            dq = min(max(dq, t0), now)
            self.trace.span(rr.trace, "queue", t0, dq,
                            request=rr.id, replica=r.index)
            self.trace.span(rr.trace, "admission", dq, now,
                            replica=r.index,
                            role=self._roles[r.index],
                            prefill_only=prefill_only)
            rr._trace_enq_t = None
        return True

    def _on_token(self, rr: RouterRequest, replica: int, tok: int) -> None:
        if rr.done or rr._replica != replica:
            return  # stale delivery from a replaced placement
        rr.tokens.append(int(tok))
        # each replica's first-ever delivery (the scale-up reaction
        # clock's far edge: decision wall time -> this entry appearing)
        self._first_token_t.setdefault(replica, time.perf_counter())
        if rr.first_token_time is None:
            rr.first_token_time = time.perf_counter()
        if rr.on_token is not None:
            rr.on_token(rr, int(tok))
        for rec in self._recovering:
            rec["pending"].discard(rr.id)
        self._gc_recovering()

    def _gc_recovering(self) -> None:
        done = [rec for rec in self._recovering if not rec["pending"]]
        for rec in done:
            self._recovering.remove(rec)
            self._stats["failover_recovery_ticks"].append(
                self._ticks - rec["start"])
            self._stats["failover_recovery_s"].append(
                round(time.perf_counter() - rec["start_t"], 4))

    def _reap(self) -> None:
        for r in self._replicas:
            assigned = self._assigned[r.index]
            for rid in [rid for rid, rr in assigned.items()
                        if rr._handle is not None and rr._handle.done]:
                rr = assigned.pop(rid)
                if rr._handle.finish_reason == "preempted":
                    # admission-pressure eviction: NOT a client-visible
                    # finish — requeue immediately (no backoff: the
                    # request did nothing wrong) and resume-from-tokens
                    # replays it losslessly when capacity frees
                    rr._handle = None
                    rr._replica = None
                    rr._eligible_at = 0.0
                    self._queue.appendleft(rr)
                    self._stats["preempted_requeues"] += 1
                    self._event("preempt_requeue", request=rr.id,
                                tenant=rr.tenant,
                                tokens_so_far=len(rr.tokens))
                    if self.trace is not None and rr.trace is not None:
                        now = time.perf_counter()
                        self.trace.span(rr.trace, "redispatch", now,
                                        now, from_replica=r.index,
                                        why="preempt")
                        rr._trace_enq_t = now
                    continue
                self._finish(rr, rr._handle.finish_reason)

    # -- prefill→decode handoff (ISSUE 12) -----------------------------

    def _handoffs(self) -> None:
        """Move every stream a prefill-role replica has parked onto a
        decode-capable replica over the KV stream. Every failure mode
        degrades to the lossless resume-from-tokens path: the first
        token was already delivered, so requeueing the RouterRequest
        replays the prompt + delivered tokens on any survivor."""
        if not self._disagg:
            return
        for src in self._replicas:
            # DRAINING sources sweep too: a scale-down target's parked
            # prefills must reach a decode home before the tombstone
            if (self._status[src.index] not in (HEALTHY, DRAINING)
                    or self._roles[src.index] != ROLE_PREFILL):
                continue
            parked = [rr for rr in self._assigned[src.index].values()
                      if rr._handle is not None
                      and getattr(rr._handle, "parked", False)
                      and not getattr(rr._handle, "done", False)]
            for rr in parked:
                self._handoff(rr, src)

    def _handoff(self, rr: RouterRequest, src) -> None:
        # target FIRST, export second: with no decode-capable home the
        # stream simply stays parked on src (its blocks intact) and the
        # sweep retries next tick — exporting eagerly would strand the
        # KV in a payload and force a full re-prefill via requeue
        tgt, tgt_key = None, None
        for r in self._replicas:
            if (self._status[r.index] != HEALTHY
                    or r.index == src.index
                    or self._roles[r.index] not in (ROLE_DECODE,
                                                    ROLE_BOTH)):
                continue
            # LIVE snapshot, not this tick's _check_health copy: the
            # drain loop runs handoffs without health sweeps, and a
            # freed decode slot must be visible there too
            try:
                h = r.health()
            except ReplicaCrashed:
                continue   # the health machinery will take it down
            if not h.get("free_slots", 0):
                continue
            key = (self._replica_score(h, None), self._placements[r.index],
                   r.index)
            if tgt_key is None or key < tgt_key:
                tgt, tgt_key = r, key
        if tgt is None:
            return   # parked, not failed: wait for a decode slot
        t_h0 = time.perf_counter()
        try:
            payload = src.export_kv(rr)
        except WireFault:
            # the transfer ABORTED mid-wire (torn/corrupt/lost payload
            # line): lossless fallback — requeue for re-prefill via
            # resume-from-tokens; the protocol-fault sweep judges src.
            # Counted + traced separately from a refused export: an
            # abort is the wire's fault, not the worker's.
            del self._assigned[src.index][rr.id]
            rr._handle = None
            rr._replica = None
            rr._eligible_at = 0.0
            self._queue.appendleft(rr)
            self._stats["handoff_aborts"] += 1
            self._event("handoff_aborted", request=rr.id,
                        from_replica=src.index, to_replica=None,
                        phase="export")
            if self.trace is not None and rr.trace is not None:
                now = time.perf_counter()
                self.trace.span(rr.trace, "redispatch", now, now,
                                from_replica=src.index,
                                why="wire_fault")
                rr._trace_enq_t = now
            return
        except (ReplicaCrashed, TimeoutError):
            # rr is still in src's assigned map — _declare_dead's
            # failover requeues it with the rest
            self._declare_dead(src, "crashed")
            return
        except ValueError:
            # the worker REFUSED the export (e.g. stale parked state
            # after a respawn): the stream no longer exists there —
            # requeue and let resume-from-tokens replay it
            del self._assigned[src.index][rr.id]
            rr._handle = None
            rr._replica = None
            rr._eligible_at = 0.0
            self._queue.appendleft(rr)
            self._stats["handoff_failures"] += 1
            self._event("handoff_failed", request=rr.id,
                        from_replica=src.index, to_replica=None)
            if self.trace is not None and rr.trace is not None:
                now = time.perf_counter()
                self.trace.span(rr.trace, "redispatch", now, now,
                                from_replica=src.index,
                                why="handoff_refused")
                rr._trace_enq_t = now
            return
        # export released the blocks on src: from here the ONLY copy of
        # the stream's KV is the payload, and the fallback is resume
        del self._assigned[src.index][rr.id]
        rr._handle = None
        rr._replica = None
        remaining = None
        if rr.deadline_s is not None:
            remaining = max(
                0.001,
                rr.deadline_s - (time.perf_counter() - rr.submit_time))

        def cb(_handle, tok, rr=rr, idx=tgt.index):
            self._on_token(rr, idx, tok)

        handle = None
        try:
            handle = tgt.import_kv(rr, payload, deadline_s=remaining,
                                   on_token=cb)
        except WireFault:
            # import reply lost/torn mid-transfer: treat as a refused
            # import (requeue below) and count the abort — the target's
            # protocol-fault sweep decides whether it stays in rotation
            self._stats["handoff_aborts"] += 1
            self._event("handoff_aborted", request=rr.id,
                        from_replica=src.index, to_replica=tgt.index,
                        phase="import")
            handle = None
        except (ReplicaCrashed, TimeoutError):
            self._declare_dead(tgt, "crashed")
            handle = None
        if handle is None:
            # the import was refused (pool pressure) or the target died
            # mid-import: requeue — resume-from-tokens replays losslessly
            rr._eligible_at = 0.0
            self._queue.appendleft(rr)
            self._stats["handoff_failures"] += 1
            self._event("handoff_failed", request=rr.id,
                        from_replica=src.index, to_replica=tgt.index)
            if self.trace is not None and rr.trace is not None:
                now = time.perf_counter()
                self.trace.span(rr.trace, "redispatch", now, now,
                                from_replica=src.index,
                                why="handoff_failed")
                rr._trace_enq_t = now
            return
        rr._handle = handle
        rr._replica = tgt.index
        rr.replicas.append(tgt.index)
        self._placements[tgt.index] += 1
        self._assigned[tgt.index][rr.id] = rr
        self._health[tgt.index]["free_slots"] = \
            self._health[tgt.index].get("free_slots", 1) - 1
        if isinstance(tgt, SubprocessReplica):
            # its cached snapshot refreshes on the next step reply;
            # debit it NOW so a same-sweep sibling handoff doesn't
            # over-commit the slot we just took
            tgt._health["free_slots"] = max(
                0, tgt._health.get("free_slots", 1) - 1)
        nbytes = payload.nbytes
        self._stats["handoffs"] += 1
        self._stats["kv_stream_bytes"] += nbytes
        self._event("handoff", request=rr.id, from_replica=src.index,
                    to_replica=tgt.index, blocks=payload.num_blocks,
                    bytes=nbytes)
        if self.trace is not None and rr.trace is not None:
            self.trace.span(rr.trace, "handoff", t_h0,
                            time.perf_counter(),
                            from_replica=src.index,
                            to_replica=tgt.index,
                            blocks=payload.num_blocks, bytes=nbytes)

    def _expire_queued_deadlines(self) -> None:
        now = time.perf_counter()
        overdue = [rr for rr in self._queue
                   if rr.deadline_s is not None
                   and now - rr.submit_time >= rr.deadline_s]
        for rr in overdue:
            self._queue.remove(rr)
            self._finish(rr, "deadline")

    def _finish(self, rr: RouterRequest, reason: str | None) -> None:
        if rr.done:
            return
        rr.done = True
        rr.finish_reason = reason or "unknown"
        rr.finish_time = time.perf_counter()
        rr._handle = None
        # "completed" counts streams that reached a SERVING conclusion
        # — shed/drained/failed refusals have their own counters and
        # must not inflate it (or the report would read 24/24 served
        # on a trace that shed 10)
        if reason in ("length", "stop", "deadline"):
            self._stats["completed"] += 1
            if rr._replica is not None:
                self._stats["served_by"][rr._replica] = \
                    self._stats["served_by"].get(rr._replica, 0) + 1
        if reason == "failed":
            self._stats["failed_requests"] += 1
        t = self._tenant_stats(rr.tenant)
        if reason in ("length", "stop", "deadline"):
            t["completed"] += 1
        elif reason == "shed":
            t["shed"] += 1
        elif reason == "failed":
            t["failed"] += 1
        if rr.ttft_s is not None:
            self._stats["ttft_s"].append(rr.ttft_s)
            t["ttft_s"].append(rr.ttft_s)
        if (self.trace is not None and rr.trace is not None
                and rr.submit_time is not None):
            # the ROOT span: every stage span parents to this one, so
            # connectivity in the merged trace is a single equality
            # check per span — and its window is what the critical-path
            # sweep tiles into queue/admission/prefill/handoff/decode/
            # stall
            self.trace.span(rr.trace, "request", rr.submit_time,
                            rr.finish_time, root=True, request=rr.id,
                            tenant=rr.tenant,
                            finish_reason=rr.finish_reason,
                            ttft_s=rr.ttft_s, retries=rr.retries)
            if reason in ("length", "stop", "deadline"):
                self.trace.note_finish(rr.tenant, rr.ttft_s)
        for rec in self._recovering:
            rec["pending"].discard(rr.id)
        self._gc_recovering()

    def _event(self, event: str, **row) -> None:
        if self.telemetry is not None:
            self.telemetry.event(event, tick=self._ticks, **row)

    # ------------------------------------------------------------------
    # lifecycle

    def warmup(self, prompt_lens=None) -> None:
        """Warm every replica (each engine compiles its tick + prefill
        buckets — in-process replicas over the same model share the jit
        cache, so N replicas compile once) and reset router stats.
        Resume-from-tokens redispatch reuses the SAME compiled prefill
        programs, so warming the buckets here is what makes a failover
        recompile-free on the survivors."""
        lens = prompt_lens or self.warmup_lens
        for r in self._replicas:
            try:
                r.warmup(lens)
            except WireFault as e:
                # a mangled (or dropped-then-timed-out) warmup reply is
                # a protocol fault, not a startup abort: the worker is
                # up and warmed — only the ACK died on the wire. Leave
                # the replica flagged; the health sweep quarantines it
                # and the clean-probe→canary path brings it back.
                self.telemetry.event("wire_fault_detected",
                                     replica=r.index, op="warmup",
                                     error=str(e))
        # subprocess workers report their engines' true context bound
        # at warmup — tighten submit validation to the real minimum
        reported = [getattr(r, "reported_max_seq_len", None)
                    for r in self._replicas]
        reported = [v for v in reported if v]
        if reported:
            self.max_seq_len = min([self.max_seq_len] + reported)
        self.reset_stats()

    def set_draft_params(self, params=None, *, checkpoint=None,
                         step=None) -> dict[int, dict]:
        """Broadcast a speculative-draft hot-swap to the whole fleet
        (ISSUE 16) — the serve half of the distill→swap loop: a
        DistillTrainer checkpoint becomes every replica's draft without
        dropping a stream (spec decode is lossless under ANY draft, so
        in-flight requests keep their token-for-token identity and their
        K/V; only the acceptance rate moves).

        In-process fleets accept a weight tree directly, or restore
        ``checkpoint`` ONCE and share the host copy; subprocess fleets
        require ``checkpoint`` — the PATH crosses the wire and each
        worker restores it through the same manifest-verified loader as
        its boot weights. Per-replica verification (tree structure +
        leaf shapes) happens in the engine either way.

        Returns {replica_index: {"draft_hash", "draft_swaps"}} for the
        replicas that accepted. A refusal (architecture mismatch) is
        counted, evented, and skipped — unless EVERY live replica
        refuses, which raises (the swap was simply wrong)."""
        if self._worker_specs is not None:
            if checkpoint is None:
                raise ValueError(
                    "a subprocess fleet takes set_draft_params("
                    "checkpoint=...) — weight trees do not cross the "
                    "wire")
            params = None   # the path is the payload
        elif params is None:
            if checkpoint is None:
                raise ValueError("pass params or checkpoint")
            from pytorchdistributed_tpu.training.checkpoint import (
                CheckpointManager,
            )

            # restore once, share the host copy fleet-wide
            with CheckpointManager(checkpoint) as mgr:
                params, _ = mgr.restore_params(step=step)
        results: dict[int, dict] = {}
        errors: list[str] = []
        for r in self._replicas:
            if self._status[r.index] in (DEAD, REMOVED):
                continue
            try:
                if params is not None:
                    info = r.set_draft_params(params)
                else:
                    info = r.set_draft_params(checkpoint=checkpoint,
                                              step=step)
            except (ReplicaCrashed, TimeoutError):
                self._declare_dead(r, "crashed")
                continue
            except ValueError as e:
                errors.append(f"replica {r.index}: {e}")
                self._event("draft_swap_failed", replica=r.index,
                            error=str(e)[:200])
                continue
            results[r.index] = info
            self._draft_info[r.index] = info
            self._stats["draft_swaps"] += 1
            self._event("draft_swap", replica=r.index,
                        hash=info.get("draft_hash"),
                        swaps=info.get("draft_swaps"),
                        checkpoint=(str(checkpoint) if checkpoint
                                    else None))
        if errors and not results:
            raise ValueError("draft swap refused fleet-wide: "
                             + "; ".join(errors[:3]))
        return results

    def run_until_idle(self, max_steps: int = 1_000_000) -> None:
        while self._queue or any(self._assigned[r.index]
                                 for r in self._replicas):
            # quarantined replicas still count: the rejoin probes that
            # could restore them only run inside step(), and so do
            # respawns — only an all-DEAD fleet with no respawn budget
            # left is genuinely unrecoverable
            if self._fleet_unrecoverable():
                raise RuntimeError(
                    "every replica is dead with work outstanding")
            if max_steps <= 0:
                raise RuntimeError("router loop did not drain")
            self.step()
            max_steps -= 1

    def stream(self, rr: RouterRequest):
        """Iterator over one request's tokens, stepping the router —
        failover happens transparently underneath; the stream just
        keeps going."""
        sent = 0
        while True:
            while sent < len(rr.tokens):
                yield rr.tokens[sent]
                sent += 1
            if rr.done:
                return
            if self._fleet_unrecoverable():
                raise RuntimeError(
                    "every replica is dead; the stream cannot finish")
            self.step()

    def request_drain(self) -> None:
        """Signal-handler-safe drain request (the run.py SIGTERM
        forwarding contract) — the next step() performs the actual
        drain outside the signal frame."""
        self._draining = True

    def install_sigterm_drain(self) -> None:
        import signal

        signal.signal(signal.SIGTERM, lambda *_: self.request_drain())

    def drain(self, max_steps: int = 100_000) -> list[RouterRequest]:
        """Graceful drain: queued requests are shed with
        ``finish_reason="drained"`` (they never started streaming —
        refusing them cleanly beats a half-stream), RESIDENT streams
        run to completion on their replicas, then nothing new is
        admitted. Returns the requests finished by the drain."""
        self._draining = True
        out: list[RouterRequest] = []
        while self._queue:
            rr = self._queue.popleft()
            self._finish(rr, "drained")
            out.append(rr)
        while any(self._assigned[r.index] for r in self._replicas
                  if self._status[r.index] in (HEALTHY, DRAINING)) \
                and max_steps:
            for r in self._replicas:
                if self._status[r.index] not in (HEALTHY, DRAINING):
                    continue
                try:
                    r.step()
                except ReplicaCrashed:
                    self._declare_dead(r, "crashed")
            # parked prefill-role streams can only finish on a decode
            # home — keep the handoff sweep alive through the drain
            self._handoffs()
            self._reap()
            max_steps -= 1
        # streams stranded on dead replicas at drain time, plus any a
        # mid-drain crash FAILED OVER back onto the queue (nothing
        # dispatches during a drain): finished with what they have —
        # the drain contract is bounded shutdown, not infinite
        # redispatch
        for r in self._replicas:
            for rr in list(self._assigned[r.index].values()):
                self._finish(rr, "drained")
                out.append(rr)
            self._assigned[r.index].clear()
        while self._queue:
            rr = self._queue.popleft()
            self._finish(rr, "drained")
            out.append(rr)
        self._event("drained", finished=len(out))
        return out

    def close(self) -> None:
        """Drain, close every replica (engines assert their pool-leak
        invariant; subprocess workers get the SIGTERM→kill_group
        escalation — no orphans), stamp the telemetry summary."""
        self.drain()
        if self.session_store is not None:
            for r in self._replicas:
                if self._status[r.index] in (HEALTHY, DRAINING):
                    self._persist_replica_sessions(r)
            # the store flushes its DRAM tier to disk (restart
            # survival) but stays open — the caller owns its lifetime
            self.session_store.flush()
        subs = [r for r in self._replicas
                if isinstance(r, SubprocessReplica)
                and self._status[r.index] != REMOVED]
        for r in self._replicas:
            if r in subs or self._status[r.index] == REMOVED:
                continue   # tombstones already closed at removal
            try:
                r.close()
            except ReplicaCrashed:
                pass
        if subs:
            # group teardown: best-effort protocol close to each, then
            # ONE kill_group escalation over the whole fleet — N wedged
            # workers cost one grace window, not N
            from pytorchdistributed_tpu.run import kill_group

            for r in subs:
                if r.alive and r.proc.poll() is None:
                    try:
                        r._drain_wire(timeout=2.0)
                        r._send({"op": "close"})
                    except (ReplicaCrashed, TimeoutError):
                        pass
            kill_group([r.proc for r in subs], grace=10.0)
            for r in subs:
                r.alive = False
                for pipe in (r.proc.stdin, r.proc.stdout):
                    try:
                        pipe.close()
                    except OSError:
                        pass
        if self._hb_dir is not None:
            import shutil

            shutil.rmtree(self._hb_dir, ignore_errors=True)
            self._hb_dir = None
        if self.telemetry is not None:
            self.telemetry.summary(**self.summary())
            self.telemetry.close()
        if self.trace is not None:
            self.trace.close()

    # ------------------------------------------------------------------
    # stats

    def reset_stats(self) -> None:
        self._stats = dict(submitted=0, completed=0, shed_requests=0,
                           failed_requests=0, failovers=0,
                           redispatched_requests=0, quarantines=0,
                           rejoins=0, hangs_detected=0, replicas_lost=0,
                           respawns=0, respawn_failures=0,
                           handoffs=0, handoff_failures=0,
                           handoff_aborts=0, wire_faults=0,
                           faults_injected=0,
                           prefix_ships=0, kv_stream_bytes=0,
                           session_reattach={"hbm": 0, "dram": 0,
                                             "disk": 0},
                           session_fallbacks=0, session_ships=0,
                           session_demotes=0,
                           scale_ups=0, scale_downs=0,
                           draft_swaps=0,
                           preemptions=0, preempted_requeues=0,
                           tenants={},
                           served_by={}, ttft_s=[],
                           failover_recovery_ticks=[],
                           failover_recovery_s=[])
        self._occ_sum = [0.0 for _ in self._replicas]
        self._occ_n = [0 for _ in self._replicas]
        self._first_token_t = {}
        self._last_signal_counts = (0, 0)

    def _tenant_stats(self, name: str) -> dict:
        t = self._stats["tenants"].get(name)
        if t is None:
            t = self._stats["tenants"][name] = dict(
                submitted=0, completed=0, shed=0, failed=0, ttft_s=[])
        return t

    @property
    def first_token_times(self) -> dict[int, float]:
        """Wall-clock time each replica delivered its FIRST token since
        the last reset_stats — the far edge of the autoscaler's
        scale-up reaction measurement (decision wall time -> the new
        replica's entry appearing here)."""
        return dict(self._first_token_t)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def in_flight(self) -> int:
        return sum(len(a) for a in self._assigned)

    def health(self) -> list[dict]:
        """The latest per-replica snapshots, status included."""
        out = []
        for r in self._replicas:
            h = dict(self._health[r.index])
            h["replica"] = r.index
            h["status"] = self._status[r.index]
            out.append(h)
        return out

    def summary(self) -> dict:
        """Router-level aggregate: request
        accounting, failover/shed/quarantine counters, per-replica
        occupancy balance and the recovery-time distribution."""
        st = self._stats
        occ = [round(self._occ_sum[i] / self._occ_n[i], 4)
               if self._occ_n[i] else None
               for i in range(len(self._replicas))]
        known = [o for o in occ if o is not None]
        ttfts = np.asarray(st["ttft_s"], np.float64)
        out = {
            "replicas": len(self._replicas),
            "healthy_replicas": sum(s == HEALTHY for s in self._status),
            "ticks": self._ticks,
            "submitted": st["submitted"],
            "completed": st["completed"],
            "shed_requests": st["shed_requests"],
            "failed_requests": st["failed_requests"],
            "failovers": st["failovers"],
            "redispatched_requests": st["redispatched_requests"],
            "quarantines": st["quarantines"],
            "rejoins": st["rejoins"],
            "hangs_detected": st["hangs_detected"],
            "replicas_lost": st["replicas_lost"],
            "respawns": st["respawns"],
            "respawn_failures": st["respawn_failures"],
            "scale_ups": st["scale_ups"],
            "scale_downs": st["scale_downs"],
            "draft_swaps": st["draft_swaps"],
            "preemptions": st["preemptions"],
            "preempted_requeues": st["preempted_requeues"],
            "statuses": list(self._status),
            "roles": list(self._roles),
            "handoffs": st["handoffs"],
            "handoff_failures": st["handoff_failures"],
            "handoff_aborts": st["handoff_aborts"],
            "wire_faults": st["wire_faults"],
            "faults_injected": st["faults_injected"],
            "prefix_ships": st["prefix_ships"],
            "kv_stream_bytes": st["kv_stream_bytes"],
            "cross_replica_hit_rate": (
                round(sum(h.get("remote_hit_tokens", 0)
                          for h in self._health)
                      / max(1, sum(h.get("admitted_tokens", 0)
                                   for h in self._health)), 4)),
            "served_by": dict(sorted(st["served_by"].items())),
            "replica_occupancy": occ,
            "occupancy_spread": (round(max(known) - min(known), 4)
                                 if known else None),
            "shed_rate": (round(st["shed_requests"]
                                / st["submitted"], 4)
                          if st["submitted"] else None),
            # recovery = failover declared -> every redispatched stream
            # delivering again. Ticks are the scheduler-step bound (the
            # chaos suite's unit); seconds are the wall-clock truth (an
            # idle router spins free ticks while the redispatch backoff
            # gate runs down, so ticks alone can over-read)
            "failover_recovery_ticks": (
                max(st["failover_recovery_ticks"])
                if st["failover_recovery_ticks"] else None),
            "failover_recovery_s": (
                max(st["failover_recovery_s"])
                if st["failover_recovery_s"] else None),
        }
        if ttfts.size:
            out["ttft_ms_p50"] = round(
                float(np.percentile(ttfts, 50)) * 1e3, 3)
            out["ttft_ms_p99"] = round(
                float(np.percentile(ttfts, 99)) * 1e3, 3)
        if self._draft_info:
            # per-replica draft identity (hash + lifetime swap count):
            # the report CLI's proof that the fleet converged on ONE
            # distilled draft after a broadcast
            out["draft"] = {
                i: dict(info)
                for i, info in sorted(self._draft_info.items())}
        if (self.session_store is not None
                or any(st["session_reattach"].values())
                or st["session_fallbacks"] or st["session_demotes"]):
            sess = {
                "reattach": dict(st["session_reattach"]),
                "fallbacks": st["session_fallbacks"],
                "ships": st["session_ships"],
                "demotes": st["session_demotes"],
                "resident": sum(h.get("sessions_resident", 0)
                                for h in self._health),
            }
            if self.session_store is not None:
                sess["store"] = self.session_store.stats()
            out["sessions"] = sess
        if st["tenants"]:
            adm = (self._admission.tenant_stats()
                   if self._admission is not None else {})
            tens = {}
            for name, t in sorted(st["tenants"].items()):
                row = {k: t[k] for k in ("submitted", "completed",
                                         "shed", "failed")}
                ts = np.asarray(t["ttft_s"], np.float64)
                if ts.size:
                    row["ttft_ms_p50"] = round(
                        float(np.percentile(ts, 50)) * 1e3, 3)
                    row["ttft_ms_p99"] = round(
                        float(np.percentile(ts, 99)) * 1e3, 3)
                if name in adm:
                    row["weight"] = adm[name]["weight"]
                    row["overage"] = adm[name]["overage"]
                tens[name] = row
            out["tenants"] = tens
        return out
