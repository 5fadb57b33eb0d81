"""Elastic recovery of a replica fleet: a worker that restores verified
weights, and a router that brings a dead replica back.

Correctness bars:

  * a replica worker's ``"checkpoint"`` spec key restores verified
    params (falling back to init_seed when absent);
  * the router's auto-respawn brings a DEAD replica back through the
    quarantine → probe → canary path with streams bitwise-preserved,
    within its budget, and declares a worker that wedges in start-up
    dead.

Engine geometry mirrors tests/test_router.py (gpt2 "test", 2 layers,
max_seq_len 64, slots 3, bucket 16) so the reference engines ride the
suite's shared jit cache. The warm start of a second process from JAX's
persistent compilation cache is tests/test_xla_cache.py's.
"""

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from pytorchdistributed_tpu.inference import generate
from pytorchdistributed_tpu.models import GPT2, gpt2_config
from pytorchdistributed_tpu.serving import ReplicaRouter

CFG = gpt2_config("test", num_layers=2, max_seq_len=64)


@functools.cache
def _setup():
    model = GPT2(CFG)
    params = model.init(jax.random.key(1), jnp.zeros((1, 4), jnp.int32))
    dm = GPT2(dataclasses.replace(CFG, decode=True))
    return model, params, dm


def _ref(prompt, n):
    _, params, dm = _setup()
    return np.asarray(generate(dm, params, jnp.asarray(prompt)[None],
                               max_new_tokens=n))[0]


def _prompts(n=4, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (m,)).astype(np.int32)
            for m in (5, 9, 7, 11, 6, 8, 4, 10)[:n]]


# ----------------------------------------------------------------------
# replica worker: the "checkpoint" spec key


def test_worker_checkpoint_key_restores_verified_params(tmp_path):
    """The replica_worker docstring's promise: a spec "checkpoint"
    loads verified weights (a TrainState-shaped checkpoint yields its
    params subtree); the engine then serves exactly those weights."""
    from pytorchdistributed_tpu.serving.replica_worker import _build_engine
    from pytorchdistributed_tpu.training.checkpoint import (
        CheckpointManager,
    )

    _, params, _ = _setup()
    state = {"step": jnp.int32(7), "params": params,
             "opt_state": {"nu": jnp.zeros(3)}}
    with CheckpointManager(tmp_path / "ckpt") as mgr:
        mgr.save(7, state)
    spec = {"model": "gpt2", "size": "test",
            "overrides": {"num_layers": 2, "max_seq_len": 64},
            "init_seed": 999,  # decoy: must NOT be used
            "checkpoint": str(tmp_path / "ckpt"),
            "engine": {"num_slots": 3, "prefill_bucket": 16}}
    eng = _build_engine(spec)
    eng.warmup(prompt_lens=(16,))
    p = _prompts(1)[0]
    r = eng.submit(p, max_new_tokens=6)
    eng.run_until_idle()
    np.testing.assert_array_equal(r.output_ids, _ref(p, 6))
    eng.close()


def test_worker_checkpoint_absent_falls_back_to_seed(tmp_path,
                                                     monkeypatch):
    """An absent/empty checkpoint must not kill the worker (it would
    die again on every respawn): it falls back to init_seed and logs
    the TelemetryEvent."""
    from pytorchdistributed_tpu.serving.replica_worker import _load_params
    from pytorchdistributed_tpu.telemetry.events import (
        EVENT_REPLICA_RESTORE_FALLBACK,
        read_events,
    )

    monkeypatch.setenv("PTD_TELEMETRY_DIR", str(tmp_path / "tele"))
    model, _, _ = _setup()
    spec = {"init_seed": 1, "checkpoint": str(tmp_path / "nope")}
    params = _load_params(spec, model)
    want = jax.jit(model.init)(jax.random.key(1),
                               jnp.zeros((1, 8), jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(jax.tree_util.tree_leaves(params)[0]),
        np.asarray(jax.tree_util.tree_leaves(want)[0]))
    kinds = [e.kind for e in read_events(tmp_path / "tele")]
    assert EVENT_REPLICA_RESTORE_FALLBACK in kinds



# ----------------------------------------------------------------------
# router auto-respawn (in-process; the subprocess e2e is full-tier)


def test_router_respawn_rejoins_and_serves(tmp_path):
    """replica_crash → DEAD → auto-respawn (budgeted, backoff) →
    QUARANTINED → clean-probe streak → canary → HEALTHY and serving
    again, with every stream — failed-over and post-respawn — bitwise
    the single-engine reference. A crash is a transient, not a
    permanent capacity loss."""
    from pytorchdistributed_tpu.faults.inject import (
        FaultInjector,
        FaultPlan,
    )
    from pytorchdistributed_tpu.faults.retry import RetryPolicy
    from pytorchdistributed_tpu.serving import HEALTHY
    from pytorchdistributed_tpu.serving.telemetry import RouterTelemetry
    from pytorchdistributed_tpu.telemetry.report import render

    model, params, _ = _setup()
    inj = FaultInjector(FaultPlan.parse("replica_crash@tick=4,replica=0"))
    router = ReplicaRouter(
        model, params, replicas=2,
        engine_kwargs=dict(num_slots=3, prefill_bucket=16),
        warmup_lens=(16, 32), faults=inj,
        respawn_budget=1, rejoin_after=2,
        respawn_policy=RetryPolicy(base_delay_s=0.0, jitter=0.0),
        telemetry=RouterTelemetry(tmp_path))
    router.warmup()
    prompts = _prompts(5)
    reqs = [router.submit(p, max_new_tokens=8) for p in prompts]
    router.run_until_idle()
    for p, r in zip(prompts, reqs):
        np.testing.assert_array_equal(r.output_ids, _ref(p, 8))
    # second wave: the respawn gate has opened by now — replica 0 comes
    # back through quarantine + canary and takes traffic again
    reqs2 = [router.submit(p, max_new_tokens=8) for p in prompts]
    router.run_until_idle()
    for p, r in zip(prompts, reqs2):
        np.testing.assert_array_equal(r.output_ids, _ref(p, 8))
    s = router.summary()
    assert s["respawns"] == 1 and s["rejoins"] == 1, s
    assert router._status[0] == HEALTHY
    reqs3 = [router.submit(p, max_new_tokens=8) for p in prompts]
    router.run_until_idle()
    assert router.summary()["served_by"].get(0, 0) > 0
    router.close()
    report = render(tmp_path)
    assert "respawns 1" in report and "respawn" in report


def test_subprocess_respawn_from_checkpoint(monkeypatch, tmp_path):
    """The acceptance chaos e2e, multi-process shape: subprocess
    workers restoring weights from a verified checkpoint; PTD_FAULTS
    crashes worker 0 from inside (os._exit mid-protocol); the router fails its
    streams over (bitwise), auto-RESPAWNS the worker — which rejoins
    through the quarantine probes and serves again with bitwise-equal
    streams — and teardown leaves no orphan. The one-shot fault marker
    persists in PTD_FAULTS_STATE, so the respawned incarnation does not
    crash-loop."""
    import time as _time

    from pytorchdistributed_tpu.faults import inject as faults_inject
    from pytorchdistributed_tpu.faults.retry import RetryPolicy
    from pytorchdistributed_tpu.serving import HEALTHY
    from pytorchdistributed_tpu.training.checkpoint import (
        CheckpointManager,
    )

    _, params, _ = _setup()
    with CheckpointManager(tmp_path / "ckpt") as mgr:
        mgr.save(1, {"step": jnp.int32(1), "params": params,
                     "opt_state": {"nu": jnp.zeros(1)}})
    monkeypatch.setenv("PTD_FAULTS", "replica_crash@tick=5,replica=0")
    monkeypatch.setenv("PTD_FAULTS_STATE", str(tmp_path / "faults"))
    faults_inject.reset_active()
    spec = {"model": "gpt2", "size": "test",
            "overrides": {"num_layers": 2, "max_seq_len": 64},
            "checkpoint": str(tmp_path / "ckpt"),
            "engine": {"num_slots": 2, "prefill_bucket": 16}}
    router = ReplicaRouter(
        workers=[spec, spec], warmup_lens=(16, 32), faults=None,
        respawn_budget=1, rejoin_after=1,
        respawn_policy=RetryPolicy(base_delay_s=0.0, jitter=0.0))
    try:
        router.warmup()
        prompts = _prompts(4)
        reqs = [router.submit(p, max_new_tokens=6) for p in prompts]
        router.run_until_idle(max_steps=200000)
        assert router.summary()["replicas_lost"] == 1
        for p, r in zip(prompts, reqs):
            np.testing.assert_array_equal(r.output_ids, _ref(p, 6),
                                          err_msg=f"request {r.id}")
        # idle-tick until the respawned worker has warmed and rejoined
        deadline = _time.time() + 180
        while (_time.time() < deadline
               and (router.summary()["respawns"] < 1
                    or router._status[0] != HEALTHY)):
            router.step()
        assert router.summary()["respawns"] == 1
        assert router._status[0] == HEALTHY
        reqs2 = [router.submit(p, max_new_tokens=6) for p in prompts]
        router.run_until_idle(max_steps=200000)
        for p, r in zip(prompts, reqs2):
            np.testing.assert_array_equal(r.output_ids, _ref(p, 6),
                                          err_msg=f"request {r.id}")
        assert router.summary()["served_by"].get(0, 0) > 0
        procs = [rep.proc for rep in router._replicas]
    finally:
        router.close()
        faults_inject.reset_active()
    deadline = _time.time() + 15
    while (_time.time() < deadline
           and any(p.poll() is None for p in procs)):
        _time.sleep(0.1)
    assert all(p.poll() is not None for p in procs), \
        [p.poll() for p in procs]


def test_respawn_warmup_timeout_declares_wedged_worker_dead():
    """A respawned worker that wedges DURING its async startup must not
    park its slot in QUARANTINED forever: past respawn_warmup_s the
    router declares it hung — spending the next budgeted attempt (or
    finally giving up) instead of silently losing capacity."""
    import time as _time

    from pytorchdistributed_tpu.serving import DEAD, QUARANTINED

    model, params, _ = _setup()
    router = ReplicaRouter(
        model, params, replicas=2,
        engine_kwargs=dict(num_slots=3, prefill_bucket=16),
        warmup_lens=(16,), faults=None, respawn_budget=1,
        respawn_warmup_s=0.01)
    router.warmup()

    class Wedged:  # a respawned subprocess worker stuck in startup
        index = 0
        hang_grace_s = 0.0
        faults_in_worker = True
        alive = True
        _warming = True

        def health(self):
            return {"alive": True, "progress": -1}

        def probe(self, exclusive=False):
            return False

        def drain(self):
            return []

        def close(self):
            pass

    router._replicas[0] = Wedged()
    router._status[0] = QUARANTINED
    router._respawns[0] = 1  # this IS the budgeted respawn, wedged
    router._warming_deadline[0] = _time.perf_counter() - 1.0
    router.step()
    assert router._status[0] == DEAD
    # budget spent: the fleet serves on the survivor, no infinite park
    p = _prompts(1)[0]
    r = router.submit(p, max_new_tokens=6)
    router.run_until_idle()
    np.testing.assert_array_equal(r.output_ids, _ref(p, 6))
    router.close()


def test_router_respawn_budget_exhausts(tmp_path):
    """With the budget spent, a crash-looping replica stays DEAD — the
    pre-ISSUE-10 behavior is the floor, and the fleet keeps serving on
    the survivor."""
    from pytorchdistributed_tpu.faults.inject import (
        FaultInjector,
        FaultPlan,
    )
    from pytorchdistributed_tpu.faults.retry import RetryPolicy
    from pytorchdistributed_tpu.serving import DEAD

    model, params, _ = _setup()
    # every rejoined incarnation of replica 0 is crashed again
    inj = FaultInjector(FaultPlan.parse(
        "replica_crash@tick=3,replica=0; replica_crash@tick=40,replica=0;"
        " replica_crash@tick=80,replica=0"))
    router = ReplicaRouter(
        model, params, replicas=2,
        engine_kwargs=dict(num_slots=3, prefill_bucket=16),
        warmup_lens=(16,), faults=inj, respawn_budget=1, rejoin_after=1,
        respawn_policy=RetryPolicy(base_delay_s=0.0, jitter=0.0))
    router.warmup()
    prompts = _prompts(4)
    for wave in range(3):
        reqs = [router.submit(p, max_new_tokens=6) for p in prompts]
        router.run_until_idle()
        assert all(r.finish_reason == "length" for r in reqs), wave
        for _ in range(30):  # spin idle ticks so chaos + respawn fire
            router.step()
    s = router.summary()
    assert s["respawns"] == 1  # budget 1: the second death is final
    assert router._status[0] == DEAD
    router.close()
