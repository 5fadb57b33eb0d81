"""Continuous-batching serving (the first layer above SURVEY.md's L6):

  * engine.py    — ServingEngine: fixed-slot KV cache + one compiled
                   decode tick + bucketed prefill-into-slot, with a
                   host-side admission/retirement scheduler and
                   per-request token streaming. ``block_size > 0``
                   switches to the PAGED engine (ISSUE 7): block-table
                   KV pool, radix prefix reuse, chunked prefill,
                   preempt-requeue. ``spec_k > 0`` adds SPECULATIVE
                   decoding (ISSUE 8): a draft model proposes k tokens
                   per slot, verified losslessly in one target forward
                   per tick (spec_decode_tick)
  * paging.py    — BlockAllocator (refcounted pool free-list, trash
                   block, leak invariant) + RadixPrefixCache
                   (block-granularity prefix trie, LRU eviction)
  * telemetry.py — ServingTelemetry: TTFT / tokens-per-s / queue depth /
                   slot occupancy / prefix-cache + block-pool metrics as
                   spans + metric JSONL through the existing telemetry/
                   package; RouterTelemetry: the router's per-replica /
                   event / summary JSONL stream
  * router.py    — ReplicaRouter (ISSUE 9): health-checked router over
                   N engine replicas (in-process or run.py-env-contract
                   subprocess workers) with lossless mid-stream
                   failover, load shedding, quarantine/rejoin and
                   graceful SIGTERM drain; replica_worker.py is the
                   subprocess side. ``roles=`` (ISSUE 12) splits the
                   fleet into prefill/decode resource classes — parked
                   prefills hand off KV blocks over the wire — and a
                   FleetPrefixIndex steers shared prefixes to the
                   replica that already holds them (or ships the
                   blocks), so a hot prefix is prefilled once per fleet
  * admission.py — AdmissionController (ISSUE 15): multi-tenant
                   admission — per-tenant queues under a priority-
                   tiered weighted-deficit-round-robin token scheduler,
                   per-tenant rate/queue caps, weighted shedding that
                   never touches a compliant tenant
  * autoscale.py — Autoscaler + SLOConfig (ISSUE 15): the control loop
                   that turns sustained SLO breaches in the router's
                   signal rings into warm add_replica / graceful
                   remove_replica, with hysteresis, cooldowns and
                   independent prefill/decode pool scaling
  * traffic.py   — seeded trace generators (steady/diurnal/flash,
                   heavy-tail lengths, shared-prefix tenant mixes,
                   multi-turn conversations with think-time gaps) and
                   the fake-clock replay()/replay_conversations()
                   drivers of the quick test tier and the soak
  * soak.py      — chaos soak (ISSUE 19): InvariantChecker (continuous
                   no-orphans / fairness / SLO-debt / zero-recompile /
                   all-streams-terminal assertions over a live fleet)
                   and run_soak(), which rides a seeded diurnal trace
                   with the autoscaler live and a faults.ChaosSchedule
                   firing rate-based replica + wire faults
  * sessions.py  — SessionStore (ISSUE 18): the host-DRAM + disk tiers
                   of the persistent-session KV hierarchy (manifest-
                   verified disk sessions, quarantine-on-corruption,
                   per-tenant caps, offline ls/verify/gc CLI); engines
                   park finished session streams in HBM, the router's
                   FleetSessionIndex steers reattaching turns to the
                   owner or pulls/seeds the payload over the wire

benchmark/drivers/serve_open_loop.py drives it open loop on the chip
(the cells of BENCHMARK.json); examples/serve.py is the train-then-serve
demo.
"""

from pytorchdistributed_tpu.serving.admission import (  # noqa: F401
    DEFAULT_TENANT,
    AdmissionController,
    TenantConfig,
)
from pytorchdistributed_tpu.serving.autoscale import (  # noqa: F401
    Autoscaler,
    SLOConfig,
)

from pytorchdistributed_tpu.serving.engine import (  # noqa: F401
    KVBlockPayload,
    PrefixBlockPayload,
    Request,
    SamplingParams,
    ServingEngine,
    decode_tick,
    kv_payload_from_wire,
    kv_payload_to_wire,
    paged_decode_tick,
    paged_prefill_chunk,
    paged_slot_models,
    prefill_into_slot,
    slot_models,
    spec_decode_tick,
)
from pytorchdistributed_tpu.serving.paging import (  # noqa: F401
    BlockAllocator,
    FleetPrefixIndex,
    FleetSessionIndex,
    RadixPrefixCache,
    block_hashes,
)
from pytorchdistributed_tpu.serving.router import (  # noqa: F401
    DEAD,
    DRAINING,
    HEALTHY,
    QUARANTINED,
    REMOVED,
    ROLE_BOTH,
    ROLE_DECODE,
    ROLE_PREFILL,
    ROLES,
    InProcessReplica,
    ReplicaCrashed,
    ReplicaRouter,
    RouterRequest,
    SubprocessReplica,
    WireFault,
)
from pytorchdistributed_tpu.serving.soak import (  # noqa: F401
    InvariantChecker,
    run_soak,
)
from pytorchdistributed_tpu.serving.telemetry import (  # noqa: F401
    ROUTER_METRICS_FILE,
    ROUTER_METRICS_GLOB,
    SERVE_METRICS_FILE,
    SERVE_METRICS_GLOB,
    RouterTelemetry,
    ServingTelemetry,
    SignalRing,
)
from pytorchdistributed_tpu.serving.sessions import (  # noqa: F401
    SessionStore,
    session_id_ok,
)
from pytorchdistributed_tpu.serving.traffic import (  # noqa: F401
    Conversation,
    ConversationTurn,
    FakeClock,
    TenantTraffic,
    TrafficRequest,
    WallClock,
    make_conversations,
    make_trace,
    replay,
    replay_conversations,
)
