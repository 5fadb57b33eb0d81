"""Train-then-SERVE demo — the continuous-batching half of the north
star's "serves heavy traffic" goal (serving/ServingEngine), on the same
tiny identity-task Llama as examples/generate.py.

Unlike the one-shot generate() call, requests here arrive staggered with
different prompt lengths, budgets and sampling params; the engine admits
each into a KV-cache slot as one frees, decodes all resident requests in
one compiled tick per step, and streams tokens per request. Run anywhere:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/serve.py --steps 200

or on TPU hardware with no flags. Pass --telemetry-dir to also get the
serving spans + metric JSONL (readable with
`python -m pytorchdistributed_tpu.telemetry merge-trace <dir>`).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import optax

import pytorchdistributed_tpu as ptd
from pytorchdistributed_tpu.models import Llama, llama_config
from pytorchdistributed_tpu.serving import SamplingParams, ServingEngine
from pytorchdistributed_tpu.training import Trainer, token_cross_entropy_loss


def main():
    parser = argparse.ArgumentParser(description="train + serve demo")
    parser.add_argument("--steps", type=int, default=200)
    parser.add_argument("--num-slots", type=int, default=3)
    parser.add_argument("--requests", type=int, default=6)
    parser.add_argument("--telemetry-dir", type=str, default=None)
    parser.add_argument("--block-size", type=int, default=0,
                        help="> 0: serve from the paged KV engine "
                             "(block-table pool + radix prefix reuse + "
                             "chunked prefill; README 'Paged KV cache')")
    parser.add_argument("--spec-k", type=int, default=0,
                        help="> 0: speculative decoding — a draft model "
                             "proposes this many tokens per target "
                             "forward, losslessly verified (README "
                             "'Speculative decoding'; implies the paged "
                             "engine, default block size 16)")
    parser.add_argument("--draft-layers", type=int, default=0,
                        help="with --spec-k: build the draft by "
                             "truncating the trained model to its first "
                             "N layers (0 = self-draft with the full "
                             "model, acceptance ~1)")
    parser.add_argument("--replicas", type=int, default=1,
                        help="> 1: serve through the health-checked "
                             "ReplicaRouter over this many in-process "
                             "engine replicas (README 'Replicated "
                             "serving & failover')")
    parser.add_argument("--prefill-replicas", type=int, default=0,
                        help="with --decode-replicas: DISAGGREGATED "
                             "topology (README 'Disaggregated serving') "
                             "— this many prefill-role replicas chunk-"
                             "prefill each prompt, then hand the KV "
                             "blocks to a decode-role replica over the "
                             "KV stream; overrides --replicas and "
                             "implies the paged engine")
    parser.add_argument("--decode-replicas", type=int, default=0,
                        help="decode-role replica count for the "
                             "disaggregated topology (see "
                             "--prefill-replicas)")
    parser.add_argument("--moe-experts", type=int, default=0,
                        help="> 0: train and serve a Switch-MoE model "
                             "(README 'Expert parallelism') — expert "
                             "kernels shard over an 'expert' mesh axis "
                             "sized from the device count, training "
                             "routes through the explicit all_to_all "
                             "dispatch, and the engine ticks on the "
                             "same dp x expert mesh")
    parser.add_argument("--autoscale", action="store_true",
                        help="serve a seeded flash-crowd trace through "
                             "the SLO autoscaler (README 'Autoscaling & "
                             "multi-tenancy'): the fleet starts at "
                             "--replicas, warm-joins replicas into the "
                             "crowd with zero fresh compiles, and "
                             "drains back to baseline after it passes")
    parser.add_argument("--tenants", type=int, default=0,
                        help="> 0: multi-tenant admission — requests "
                             "carry round-robin tenant tags (t0 gets a "
                             "10x share under --autoscale), the WDRR "
                             "scheduler keeps the token split weighted-"
                             "fair, and the summary prints the per-"
                             "tenant table")
    parser.add_argument("--sessions", action="store_true",
                        help="multi-turn demo (README 'Persistent "
                             "sessions & KV tiering'): a seeded "
                             "conversation mix replayed through a "
                             "sessioned router — later turns REATTACH "
                             "the parked KV (HBM or the store's DRAM "
                             "tier) instead of re-prefilling; implies "
                             "the paged engine and the router path")
    parser.add_argument("--trace", action="store_true",
                        help="fleet-wide request tracing (README "
                             "'Distributed request tracing'): every "
                             "request carries a TraceContext through "
                             "queue/admission/prefill/handoff/decode, "
                             "and the run ends with the per-stage "
                             "critical-path + SLO-debt report (needs "
                             "--telemetry-dir; serves via the router)")
    parser.add_argument("--chaos", action="store_true",
                        help="with --replicas > 1: crash replica 0 "
                             "mid-trace — watch the router redispatch "
                             "its streams to a survivor with the SAME "
                             "tokens")
    args = parser.parse_args()

    from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache

    use_persistent_cache()

    if args.sessions:
        if args.autoscale:
            parser.error("--sessions and --autoscale are separate "
                         "demos — run them one at a time")
        if not args.block_size:
            args.block_size = 16  # sessions require the paged engine
    if args.trace and not args.telemetry_dir:
        parser.error("--trace needs --telemetry-dir (spans are "
                     "trace_rank*.jsonl files in the run dir)")
    if args.spec_k and not args.block_size:
        args.block_size = 16  # spec requires the paged engine
    roles = None
    if args.prefill_replicas or args.decode_replicas:
        if not (args.prefill_replicas and args.decode_replicas):
            parser.error("--prefill-replicas and --decode-replicas go "
                         "together (a disaggregated fleet needs both "
                         "halves)")
        if args.spec_k:
            parser.error("--spec-k and the disaggregated topology are "
                         "mutually exclusive (KV handoff carries no "
                         "draft state)")
        from pytorchdistributed_tpu.serving import ROLE_DECODE, ROLE_PREFILL

        roles = ([ROLE_PREFILL] * args.prefill_replicas
                 + [ROLE_DECODE] * args.decode_replicas)
        args.replicas = len(roles)
        if not args.block_size:
            args.block_size = 16  # KV handoff requires the paged engine

    ptd.init_process_group()
    mesh, moe_kw, loss = ptd.create_mesh(), {}, token_cross_entropy_loss
    if args.moe_experts:
        if args.replicas > 1 or roles:
            parser.error("--moe-experts serves through one expert-sharded "
                         "engine (replicated/disaggregated topologies "
                         "would need per-replica meshes)")
        import jax

        from pytorchdistributed_tpu.runtime.mesh import MeshConfig
        from pytorchdistributed_tpu.training import (
            moe_token_cross_entropy_loss,
        )

        ndev = jax.device_count()
        ep = next((e for e in (4, 2, 8)
                   if ndev % e == 0 and args.moe_experts % e == 0), 1)
        mesh = ptd.create_mesh(MeshConfig(data=ndev // ep, expert=ep))
        moe_kw = dict(moe_experts=args.moe_experts)
        loss = moe_token_cross_entropy_loss
    cfg = llama_config("test", max_seq_len=64, **moe_kw)
    model = Llama(cfg)
    trainer = Trainer(model, optax.adamw(3e-3), loss,
                      mesh=mesh, strategy="dp", log_every=50)

    # identity task: target[t] = token[t] — greedy serving visibly repeats
    # each prompt's last token (the learned behavior), so mixed-length
    # continuations are easy to eyeball
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg.vocab_size, (32, 32)).astype(np.int32)
    batch = {"tokens": tokens, "targets": tokens.copy()}
    for _ in range(args.steps):
        metrics = trainer.train_step(batch)
        float(metrics["loss"])  # force the async dispatch each step
    print(f"trained {args.steps} steps, loss {float(metrics['loss']):.4f}")

    params = {"params": trainer.state.params["params"]}
    spec_kw = {}
    if args.spec_k and args.draft_layers:
        from pytorchdistributed_tpu.inference import truncated_draft

        draft, draft_params = truncated_draft(model, params,
                                              args.draft_layers)
        spec_kw = dict(draft_config=draft.cfg, draft_params=draft_params)

    if (args.replicas > 1 or args.autoscale or args.tenants
            or args.trace or args.sessions):
        # REPLICATED serving (ISSUE 9): the router owns N engines,
        # balances on their health snapshots and — with --chaos — shows
        # lossless mid-stream failover: the crashed replica's streams
        # resume on a survivor with identical tokens. --autoscale /
        # --tenants (ISSUE 15) ride the same router path, so a
        # 1-replica fleet works too.
        from pytorchdistributed_tpu.serving import ReplicaRouter

        # no --chaos: leave the router's default ("auto") so the
        # PTD_FAULTS env contract keeps working through the demo
        router_kw = {}
        if args.trace:
            # request tracing (ISSUE 17): every submit mints a
            # TraceContext; the run ends with the merged critical-path
            # report over trace_rank*.jsonl
            router_kw["trace"] = True
        if args.chaos:
            # the supported chaos contract — the same spec syntax
            # `run.py --faults` / PTD_FAULTS accept; the router fires
            # it at its own tick counter (one submit = one tick here,
            # so this kills replica 0 mid-trace)
            from pytorchdistributed_tpu.faults import (
                FaultInjector,
                FaultPlan,
            )

            spec = (f"replica_crash@tick={max(2, args.requests // 2)},"
                    f"replica=0")
            print(f"--- chaos armed: {spec} ---")
            router_kw["faults"] = FaultInjector(FaultPlan.parse(spec))
        store = None
        if args.sessions:
            # persistent sessions (ISSUE 18): the router owns the
            # host-DRAM store tier; engines park finished session
            # streams in HBM and demote the eldest into it
            from pytorchdistributed_tpu.serving import SessionStore

            store = SessionStore(None, dram_bytes=64 << 20)
            router_kw["session_store"] = store
        names = ["default"]
        if args.tenants:
            # equal WDRR weights: fairness comes from the scheduler,
            # not from handicapping the hot tenant's quota
            from pytorchdistributed_tpu.serving import TenantConfig

            names = [f"t{i}" for i in range(args.tenants)]
            router_kw["tenants"] = {n: TenantConfig(weight=1.0)
                                    for n in names}
        router = ReplicaRouter(
            model, params, replicas=args.replicas, roles=roles,
            engine_kwargs=dict(num_slots=args.num_slots,
                               prefill_bucket=16,
                               block_size=args.block_size,
                               spec_k=args.spec_k, **spec_kw),
            warmup_lens=(16,), telemetry_dir=args.telemetry_dir,
            **router_kw)
        router.warmup()
        router.install_sigterm_drain()
        if args.autoscale:
            # a seeded flash crowd on the fake-clock replay driver: the
            # autoscaler warm-joins replicas into the breach (zero
            # fresh compiles — in-process joins share the jit cache)
            # and the post-crowd drain removes them gracefully
            from pytorchdistributed_tpu.serving import (
                Autoscaler,
                FakeClock,
                SLOConfig,
                TenantTraffic,
                make_trace,
                replay,
            )

            mix = tuple(
                TenantTraffic(n, share=(10.0 if i == 0 and len(names) > 1
                                        else 1.0))
                for i, n in enumerate(names))
            trace = make_trace(
                seed=0, duration_s=3.0, base_qps=4.0, shape="flash",
                peak_mult=20.0, tenants=mix,
                vocab_size=cfg.vocab_size, prompt_cap=12, new_cap=8)
            clk = FakeClock()
            # TTFT is wall-clock, not fake-clock — neutralized so host
            # step timing isn't a control input in a demo run
            asc = Autoscaler(
                router,
                SLOConfig(queue_high=3.0, shed_rate_max=1.0,
                          ttft_target_ms=1e9),
                min_replicas=args.replicas,
                max_replicas=args.replicas + 2, breach_ticks=2,
                clear_ticks=25, up_cooldown_s=0.3, down_cooldown_s=0.2,
                clock=clk)
            print(f"--- flash crowd: {len(trace)} requests over "
                  f"{sorted({t.tenant for t in trace})} ---")
            reqs = replay(router, trace, clock=clk, tick_s=0.02,
                          autoscaler=asc)
            for _ in range(3000):   # drain back down to baseline
                router.step()
                asc.step()
                clk.advance(0.02)
                st = router.pool_state()["fleet"]
                if (st["healthy"] == args.replicas
                        and st["draining"] == 0):
                    break
            for d in asc.decisions:
                print(f"  {d['action']} replica={d['replica']} "
                      f"why={','.join(d['why'])} "
                      f"queue={d['m_queue_depth']:.1f}")
            done = sum(1 for r in reqs if r.finish_reason
                       in ("length", "stop"))
            print(f"served {done}/{len(reqs)} "
                  f"(shed {sum(1 for r in reqs if r.finish_reason == 'shed')})")
            print("autoscaler summary:", asc.summary())
        elif args.sessions:
            # a seeded multi-turn mix on the fake-clock replay driver:
            # each turn submits only after the previous finished and
            # its think gap elapsed, carrying the full history — later
            # turns reattach the parked KV instead of re-prefilling
            from pytorchdistributed_tpu.serving import (
                make_conversations,
                replay_conversations,
            )

            convs = make_conversations(
                seed=0, duration_s=6.0, session_rate=0.8,
                vocab_size=cfg.vocab_size, turns_cap=4, turn_cap=10,
                new_cap=6, think_mean_s=0.3)
            print(f"--- {len(convs)} conversations, "
                  f"{sum(len(c.turns) for c in convs)} turns ---")
            out = replay_conversations(router, convs, tick_s=0.02,
                                       max_seq_len=cfg.max_seq_len)
            for c in convs:
                for t, r in enumerate(out[c.session_id]):
                    hops = "->".join(map(str, r.replicas))
                    print(f"  {c.session_id} turn {t} (replica {hops},"
                          f" {r.finish_reason}): "
                          f"{len(r.prompt)} ctx -> {list(r.tokens)}")
            sess = router.summary().get("sessions", {})
            print(f"session reattaches {sess.get('reattach')} "
                  f"fallbacks {sess.get('fallbacks')} "
                  f"demotes {sess.get('demotes')}")
        else:
            reqs = []
            for i in range(args.requests):
                prompt = rng.integers(1, cfg.vocab_size,
                                      (int(rng.integers(3, 12)),)
                                      ).astype(np.int32)
                sampling = (SamplingParams() if i % 2 == 0 else
                            SamplingParams(temperature=0.7, top_k=8,
                                           seed=i))
                reqs.append(router.submit(prompt, max_new_tokens=8,
                                          sampling=sampling,
                                          tenant=names[i % len(names)]))
                router.step()
            router.run_until_idle()
            for r in reqs:
                hops = "->".join(map(str, r.replicas))
                print(f"req {r.id} (replica {hops}, {r.tenant}, "
                      f"{r.finish_reason}, retries {r.retries}): "
                      f"{r.prompt.tolist()} -> {r.tokens}")
        print("router summary:", router.summary())
        router.close()
        if store is not None:
            print("session store:", store.stats())
            store.close()
        if args.trace:
            from pytorchdistributed_tpu.telemetry.tracing import (
                render_trace,
            )

            print()
            print(render_trace(args.telemetry_dir, top=args.requests))
        ptd.destroy_process_group()
        return

    engine = ServingEngine(
        model, params,
        num_slots=args.num_slots, prefill_bucket=16,
        block_size=args.block_size, spec_k=args.spec_k, **spec_kw,
        mesh=mesh if args.moe_experts else None,
        telemetry_dir=args.telemetry_dir)
    engine.warmup(prompt_lens=(16,))

    # staggered mixed-length traffic: more requests than slots, per-request
    # budgets and sampling — the queue drains as slots retire
    reqs = []
    for i in range(args.requests):
        prompt = rng.integers(1, cfg.vocab_size,
                              (int(rng.integers(3, 12)),)).astype(np.int32)
        sampling = (SamplingParams() if i % 2 == 0 else
                    SamplingParams(temperature=0.7, top_k=8, seed=i))
        reqs.append(engine.submit(prompt, max_new_tokens=8,
                                  sampling=sampling))
        engine.step()  # arrivals interleave with decoding
    engine.run_until_idle()

    for r in reqs:
        print(f"req {r.id} (slot {r.slot}, {r.finish_reason}, "
              f"ttft {r.ttft_s * 1e3:.1f} ms): "
              f"{r.prompt.tolist()} -> {r.new_tokens}")
    print("summary:", engine.summary())
    engine.close()
    ptd.destroy_process_group()


if __name__ == "__main__":
    main()
