"""The serving driver: `ReplicaRouter.submit` / `.step` over one
in-process `ServingEngine` replica, under an open loop.

One thread offers the load and steps the router: every request is sent
when the schedule says it is due (or as soon after as the loop comes
round; how late is reported), and its latency counts from when it was
due. Load is offered from `ramp_s` before the window; what was due before
the window loads the system and enters no metric.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from benchmark import loadgen, reference, window
from benchmark.drivers import common


class ServeSystem:
    """Router, engine and weights of one cell; many windows can be run on
    one of these (the knee sweep does)."""

    def __init__(self, cell, devices, seed: int, phases=None):
        from pytorchdistributed_tpu.serving import (
            ReplicaRouter,
            ServingEngine,
        )

        cfg, mix = cell.config, cell.mix
        self.cfg, self.mix, self.devices = cfg, mix, devices
        fam = cell.family
        model = fam.program_model(cfg, mix)
        self.make_params = jax.jit(lambda s: fam.to_program_tree(
            fam.make_weights(cfg, s), cfg, mix))
        params = self.make_params(reference.seed_u32(seed))
        jax.block_until_ready(params)
        if phases:
            phases.mark("weights")
        self.engines = []

        def factory():
            # every key of the mix's `engine` by name: a mix sizes the
            # slots, the blocks and the pool
            self.engines.append(ServingEngine(model, params,
                                              **mix["engine"]))
            return self.engines[-1]

        self.router = ReplicaRouter(factories=[factory])
        self.engine = self.engines[0]
        if phases:
            phases.mark("build")
        # the tick and the one chunk shape are all this traffic uses
        self.engine.warmup()
        self.router.reset_stats()
        if phases:
            phases.mark("warm-up")

    def queued(self) -> int:
        return int(self.router.queue_depth + self.engine.queue_depth
                   + self.engine.prefilling_count)

    def busy(self) -> bool:
        return bool(self.queued() or self.engine.active_count
                    or self.router.in_flight)

    def close(self) -> None:
        self.router.close()
        self.router = self.engine = None
        self.engines = []
        gc.collect()


def offer(system: ServeSystem, trace: list, seconds: float, *,
          tracer=None, trace_at=None, phases=None) -> dict:
    """Ramp, window and drain of one run. Returns the records and the
    counters read at the window's edges."""
    mix, router, engine = system.mix, system.router, system.engine
    timeout = float(mix["first_token_timeout_s"])
    records = [window.RequestRecord(
        due=a.due_s, in_window=a.in_window, prompt_len=len(a.prompt),
        max_new_tokens=a.max_new_tokens) for a in trace]
    start = time.perf_counter()
    t0 = start + float(mix["ramp_s"])
    t1 = t0 + seconds
    for r in records:
        r.due += t0
    nxt = 0
    counters: dict = {}
    in_window = False

    def on_token(rec):
        def cb(_rr, tok):
            rec.token_times.append(time.perf_counter())
        return cb

    def submit_due(now):
        nonlocal nxt
        while nxt < len(records) and records[nxt].due <= now:
            rec, arr = records[nxt], trace[nxt]
            with jax.profiler.TraceAnnotation("loadgen.submit"):
                rec.handle = router.submit(
                    arr.prompt, max_new_tokens=arr.max_new_tokens,
                    on_token=on_token(rec))
            rec.sent = time.perf_counter()
            nxt += 1

    while True:
        now = time.perf_counter()
        if not in_window and now >= t0:
            in_window = True
            engine.reset_stats()
            counters["queued_t0"] = system.queued()
            counters["active_t0"] = engine.active_count
            if phases:
                phases.window_start(t0)
        if now >= t1:
            # what fell due during the window's last step is sent, late,
            # and waits: it is backlog, not a request that failed
            submit_due(t1)
            break
        if tracer is not None and in_window:
            tracer.poll(now - t0, trace_at)
        submit_due(now)
        if system.busy():
            with jax.profiler.TraceAnnotation("router.step"):
                router.step()
        else:
            wait = (records[nxt].due if nxt < len(records) else t1) - now
            with jax.profiler.TraceAnnotation("loadgen.wait"):
                time.sleep(max(0.0, min(wait, 0.002)))
    counters["queued_t1"] = system.queued()
    counters["active_t1"] = engine.active_count
    counters["engine"] = {k: v for k, v in engine.summary().items()
                          if not isinstance(v, (dict, list))}
    if tracer is not None:
        tracer.finish()
    # after the window no new load is offered; step on until every
    # request that was due in it has its first token, or the time-out
    # makes it failed
    if mix.get("drain", False):
        deadline = t1 + timeout
        while time.perf_counter() < deadline and any(
                r.in_window and r.sent is not None and not r.token_times
                for r in records):
            router.step()
    for r in records:
        h = r.handle
        if h is not None and h.done:
            r.finish_reason = h.finish_reason
    return {"records": records, "t0": t0, "t1": t1, "counters": counters}


def plant_altered_token(system: ServeSystem, vocab: int) -> None:
    """The fault the harness's own test plants: the engine delivers (and
    feeds back) another token than the one it sampled, as the second
    token of every request."""
    engine = system.engine
    deliver = engine._deliver

    def altered(req, tok):
        if len(req.new_tokens) == 1:
            tok = (int(tok) + 1) % vocab
        return deliver(req, tok)

    engine._deliver = altered


def sample_finished(records, seed: int, k: int) -> list:
    """The requests the comparison reads: the longest finished one and
    k - 1 more, drawn from the seed."""
    done = [r for r in records
            if r.finish_reason == "length" and r.token_times
            and len(r.token_times) == r.max_new_tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: r.prompt_len + r.max_new_tokens)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC0DE])
    picks = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in picks]


def served_gaps(cell, devices, seed: int, sample: list) -> dict:
    """How far the served tokens lie below the reference's best logit,
    over the sample: the widest gap, the mean gap (all served tokens in
    the denominator) and the share of tokens that are not the
    reference's first choice; and what the int8 reference control would
    read on the same prompts and tokens (`control_*`)."""
    ref = reference.ServeReference(cell.family, cell.config, devices)
    ref.load(seed)
    got_g, ctl_g = [], []
    for r in sample:
        got = np.asarray(r.handle.tokens, np.int32)
        g, c = ref.gaps(np.asarray(r.handle.prompt), got)
        got_g.append(g)
        ctl_g.append(c)
    g, c = np.concatenate(got_g), np.concatenate(ctl_g)
    return {"served_gap": float(g.max()),
            "served_mean_gap": float(g.mean()),
            "served_flip_share": float((g > 0).mean()),
            "control_gap": float(c.max()),
            "control_mean_gap": float(c.mean()),
            "control_flip_share": float((c > 0).mean()),
            "tokens": int(g.size), "requests": len(sample)}


def run(cell, devices, args, phases, fault=None) -> dict:
    mix = cell.mix
    system = ServeSystem(cell, devices, args.seed, phases)
    trace = loadgen.serve_trace(mix, cell.config["vocab_size"], args.seed,
                                args.seconds)
    trace_at = None
    if args.trace:
        # the last seconds of the window: stopping the profiler blocks
        # the host for a while, and there it delays nothing
        trace_at = (args.seconds - float(mix["trace_s"]),
                    float(mix["trace_s"]))
    if fault == "token_altered":
        plant_altered_token(system, cell.config["vocab_size"])
    out = offer(system, trace, args.seconds, tracer=args.tracer,
                trace_at=trace_at, phases=phases)
    records, t0, t1 = out["records"], out["t0"], out["t1"]
    timeout = float(mix["first_token_timeout_s"])
    metrics = window.serve_metrics(records, t0, t1, timeout)
    peak = common.memory_peak_bytes(devices)
    pool_bytes = int(system.engine.kv_hbm_bytes)
    system.close()
    phases.note("window closed; running the reference")
    sample = sample_finished(records, args.seed,
                             int(mix["compare_requests"]))
    in_win = [r for r in records if r.in_window]
    # failed: never sent, ended by anything but its length or a stop id,
    # or (where the run waits for first tokens) still without one at the
    # time-out. A request still queued when a saturated cell's window
    # closes is unfinished, not failed.
    bad = [r for r in in_win if r.sent is None
           or r.finish_reason not in (None, "length", "stop")
           or (mix.get("drain", False) and not r.token_times)]
    limits = mix["limits"]
    if sample:
        cmp = served_gaps(cell, devices, args.seed, sample)
        checks = [(k, cmp[k], limits[k]) for k in
                  ("served_gap", "served_mean_gap", "served_flip_share")
                  if k in limits]
    else:  # nothing finished: nothing shown to be right
        cmp = {"tokens": 0, "requests": 0}
        checks = [(k, float("inf"), v) for k, v in limits.items()]
    sizes = loadgen.multiset_sizes(mix, args.seconds)
    return {
        "metrics": metrics, "checks": checks, "attempted": len(in_win),
        "failed": len(bad), "memory_peak_bytes": int(peak),
        "t0": t0, "t1": t1,
        "log": {"rate_rps": mix["rate_rps"], **sizes,
                "slots": mix["engine"]["num_slots"],
                "kv_pool_bytes": pool_bytes,
                "finished": sum(1 for r in records if r.finish_reason),
                "first_tokens_in_window": sum(
                    1 for r in in_win if r.token_times),
                "gen_lateness_p95_ms": window.lateness_p95_ms(records),
                "compared_requests": cmp["requests"],
                "compared_tokens": cmp["tokens"],
                **{k: cmp.get(k) for k in (
                    "served_gap", "served_mean_gap", "served_flip_share",
                    "control_gap", "control_mean_gap",
                    "control_flip_share")},
                **{k: v for k, v in out["counters"].items()
                   if k != "engine"}},
        "ctx": {"records": records, "counters": out["counters"]},
    }
