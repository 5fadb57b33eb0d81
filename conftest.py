"""Pytest bootstrap: run all tests on an 8-device CPU simulation.

This is the TPU analog of the reference's "gloo CPU smoke" config
(BASELINE.json configs[0]): `--xla_force_host_platform_device_count=8` gives
a single process 8 XLA CPU devices, so every pjit/shard_map code path —
including multi-chip sharding — executes without TPU hardware (SURVEY.md §4).

Must run before the first `import jax` anywhere in the test session.
"""

import os

# Tests never run on an accelerator: the suite must be hermetic and
# multi-"chip", so the platform is forced here, whatever the environment
# says. Running on the chip is chip_smoke.py's job, not pytest's.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

assert jax.default_backend() == "cpu", "tests must run on the CPU simulator"

# Persistent XLA compile cache: repeat suite runs skip recompiling
# unchanged programs (the compiled-invariant tripwires lower flagship-width
# steps — ~30-100 s each cold, seconds warm). Keyed on the optimized HLO,
# so a genuine program change always recompiles. Placed by the one rule
# every entry point shares (runtime/xla_cache.py). The 5 s floor keeps
# the suite's thousands of sub-second programs out of it.
from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache

use_persistent_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "quick: fast smoke subset (target <3 min on 1 core; "
        "every test not marked slow)")
    config.addinivalue_line(
        "markers", "slow: heavyweight tests excluded from -m quick")


# The `-m quick` smoke allowlist (VERDICT r3 #9): one fast representative
# per subsystem, curated so the subset runs in <3 min on the 1-core driver
# rig (the full suite takes ~24 min there). Matched by substring; kept
# central — one list to re-tune instead of decorators across 17 files.
_QUICK = (
    "test_data.py::TestShardedSampler",       # sampler contract (numpy)
    "test_data.py::TestDatasets",
    "test_data.py::TestDataLoader",
    "test_norms.py",                          # fused-norm equivalence
    # a model with two cache kinds through the paged engine against the
    # benchmark's plain reference (ISSUE 30)
    "test_latent_serving.py",
    # EVA attention (a tumbling window beside a summary row a chunk)
    # through the paged engine's two pools against the benchmark's plain
    # reference, and the pools' books (ISSUE 34)
    "test_eva_serving.py",
    # layers of two kinds in one scanned stack (full NoPE layers, RoPE
    # window layers) over two K/V pools, grouped heads and softmax-routed
    # ReGLU experts routed before attention, through the paged engine
    # against the benchmark's plain reference, with its planted faults
    # (ISSUE 36)
    "test_smallthinker_serving.py",
    # Mamba-1 mixers beside NoPE attention in one scanned stack, a
    # recurrent state a slot beside the K/V pool, through the paged engine
    # against the benchmark's plain reference, its planted faults and
    # refusals (ISSUE 40; ~2.5 min), and the selective-scan kernel
    # (interpreted) against `lax.scan`
    "test_jamba_serving.py",
    "test_ssm_scan.py",
    # the parallel block (one LayerNorm without a bias for attention and
    # the experts), sigmoid-routed experts of which a share is held beside
    # averaged shared experts, GPT-J RoPE window layers and NoPE full
    # layers over two K/V pools and a tied head, through the paged engine
    # against the benchmark's plain reference, its planted faults, the
    # eight expert-parallel shares summed, the chunks' device counters,
    # the query block sized by the heads and a chunk of many heads through
    # the flash kernel (interpreted)
    "test_cohere2_serving.py",
    # the experts' grouped product: the kernel that reads a bank where it
    # lies in a stack (interpreted) against `lax.ragged_dot` on the
    # slice, and the scanned stack handing it its banks whole (ISSUE 37;
    # ~1 min together)
    "test_grouped_matmul.py",
    # the engine's weights in the compute type, cast once where a tree is
    # taken: bitwise tokens and logits, no convert in the tick (ISSUE 31)
    "test_serving_weights.py",
    "test_utils.py",                          # meters, guards, trace tools
    "test_mesh.py",                           # mesh/axis construction
    "test_auto.py",                           # sharding-ladder planner
    "test_native.py",                         # C++ gather + ctypes fallback
    "test_config.py::test_cli",               # flag parsing (no model init)
    "test_trainer.py::test_reference_training_job_runs",  # e2e 8-dev DDP
    "test_trainer.py::test_accum_steps_validations",
    "test_trainer.py::test_dp_equivalence_8dev_vs_1dev",
    "test_trainer.py::test_evaluate_matches_train_loss",
    # under 2 s each, and they guard Trainer.train_step (ISSUE 32: a
    # test under 2 s that guards a measured path runs)
    "test_trainer.py::test_loss_decreases",
    "test_trainer.py::test_fsdp_actually_shards_params",
    "test_trainer.py::test_bf16_policy_trains",
    "test_trainer.py::test_watchdog_kills_training_on_nan",
    "test_trainer.py::test_watchdog_off_by_flag",
    "test_trainer.py::test_profile_flag_writes_trace",
    "test_trainer.py::test_throughput_meter_feeds_logging",
    "test_trainer.py::test_fit_with_val_loader_reports_val_metrics",
    "test_trainer.py::test_batch_adapter_multi_input_model",
    "test_trainer.py::test_trainer_beats_heartbeat_at_device_sync",
    "test_trainer.py::test_multi_replica_eval_ignores_padding",
    "test_trainer.py::test_evaluate_pad_weights_ignore_claimed_batch_size",
    "test_trainer.py::test_evaluate_warns_when_custom_loss_ignores_sample_weight",
    "test_trainer.py::test_evaluate_asserts_loader_sampler_alignment",
    "test_trainer.py::test_unknown_batch_keys_error_mentions_adapter",
    "test_pipeline.py::test_gpipe_spmd_matches_sequential",
    "test_pipeline.py::test_one_f_one_b_matches_sequential_grads",
    "test_attention.py::test_flash_matches_dense",  # Pallas kernel math
    "test_quant.py::TestQuantDot",            # int8 quant-dot numerics
    "test_quant.py::test_parity_dp",          # int8_fwd vs bf16 loss curve
    "test_moe.py::test_single_expert_is_dense_mlp",
    "test_moe.py::test_moe_aux_loss_uniform_at_balance",
    # expert-parallel MoE (ISSUE 14): a2a-vs-dense parity (fp32 roundoff,
    # int8 tol), chunked-overlap bitwise, top-2 per-token reference +
    # the k-major capacity-race edge, and the expert-sharded serving
    # bitwise + zero-recompile tripwire
    "test_moe.py::test_expert_parallel_a2a_matches_single_device",
    "test_moe.py::test_expert_parallel_int8_parity",
    "test_moe.py::test_moe_chunked_overlap_bitwise",
    "test_moe.py::test_top2_matches_per_token_reference",
    "test_moe.py::test_top2_first_choices_win_capacity_race",
    "test_moe.py::test_moe_serving_bitwise_vs_generate_expert_sharded",
    # torch->TPU logit parity — everything except the resnet EVALUATE
    # smoke (~33 s: full eval loop on imported weights; the bitwise
    # logits parity test right above it already pins import
    # correctness, so the smoke rides the full tier — tier-1 sits AT
    # the 870 s budget and this is the lowest-marginal-value block)
    "test_torch_import.py::test_gpt2_import_matches_torch_logits",
    "test_torch_import.py::test_generate_on_imported_weights_matches_torch_greedy",
    "test_torch_import.py::test_llama_import_matches_torch_logits",
    "test_torch_import.py::test_bert_import_matches_torch_logits",
    "test_torch_import.py::test_vit_import_matches_torch_logits",
    "test_torch_import.py::test_imported_weights_survive_checkpoint_roundtrip",
    "test_torch_import.py::test_llama_import_rejects_tied_embeddings",
    "test_torch_import.py::test_resnet50_import_matches_torch_logits",
    "test_torch_import.py::test_resnet50_import_rejects_same_padding_config",
    "test_torch_import.py::test_resnet50_import_rejects_class_mismatch",
    "test_torch_import.py::test_llama_import_rejects_eps_mismatch",
    # telemetry subsystem: tracer/accounting/tripwire units + the
    # single-process end-to-end smoke (train with telemetry on → report);
    # the 2-process report run stays full-suite-only
    "test_telemetry.py::test_span_tracer_chrome_roundtrip",
    "test_telemetry.py::test_span_overhead_under_budget",
    "test_telemetry.py::test_collective_bytes_parses_shapes",
    "test_telemetry.py::test_step_accounting_mlp_hand_computed",
    "test_telemetry.py::test_anomaly_detector_non_finite_and_spike",
    "test_telemetry.py::test_tripwires_fire_on_injected_nan_loss",
    "test_telemetry.py::test_telemetry_smoke_end_to_end",
    # compiled-artifact tripwires: the structural (test-size) tier; the
    # flagship-width tier stays full-suite-only (CPU compiles are
    # ~30-100 s each cold)
    "test_compiled_invariants.py::test_structural_invariants",
    # latency-hiding collectives (ISSUE 5): ring-primitive numerics +
    # routing/fallback units, the fp32 and int8 tp parity anchors, the
    # zero-recompile tripwire, the census parser unit and the satellite
    # units (ring_schedule / all_to_all validation / prefetch depth +
    # Trainer knobs) plus the structural comm_stall_frac pins; the bf16
    # parity trio and the census-decomposition test stay full-suite-only
    # (each builds multiple trainers) — quick-tier ring-census coverage
    # is the committed tp4_dp2_ring* pins in test_structural_invariants
    "test_overlap.py::TestRingPrimitives",
    "test_overlap.py::TestRouting",
    "test_overlap.py::test_parity_tp_fp32_exact",
    "test_overlap.py::test_parity_tp_int8",
    "test_overlap.py::test_zero_steadystate_recompiles",
    "test_overlap.py::test_overlap_census_parses_async_pairs",
    "test_overlap.py::test_ring_schedule",
    "test_overlap.py::test_all_to_all_validates_axes",
    "test_overlap.py::test_prefetch_depth_zero_is_synchronous",
    "test_overlap.py::test_trainer_prefetch_knob",
    "test_compiled_invariants.py::test_comm_stall_frac_pinned",
    # serving engine (ISSUE 3): the HLO pins for the tick/prefill pair
    # (+--quant variants), the greedy-parity-vs-generate() anchor, the
    # zero-recompile steady-state guarantee, and the generate() bucketing
    # retrace tripwire; the tp-mesh / stress / telemetry serving tests
    # stay full-suite-only (multi-second compiles)
    "test_compiled_invariants.py::test_serving_invariants",
    "test_serving.py::test_parity_greedy_gpt2",
    "test_serving.py::test_zero_recompiles_steady_state",
    "test_inference.py::test_bucketed_trace_count_regression",
    # the sampler's candidate search (ISSUE 39): `lax.top_k` bit for bit
    # over widths on both sides of its shape rule and rows made to break
    # a search by groups (~10 s: one compile a width)
    "test_inference.py::test_top_candidates",
    "test_inference.py::test_wide_vocabulary_samples",
    # faults/chaos subsystem (ISSUE 4): spec/retry/injector units plus
    # the two single-process fault-injection picks (nan tripwire+watchdog,
    # corrupt-latest fallback + verify CLI) and the injected ckpt_corrupt
    # hook; the run.py multi-process chaos scenarios (crash-resume
    # continuity, hang relaunch, preemption, signal forwarding) stay
    # full-suite-only — each spawns real worker processes
    "test_faults.py::TestFaultPlan",
    "test_faults.py::TestRetry",
    "test_faults.py::TestInjector",
    "test_faults.py::test_nan_injection_trips_watchdog",
    "test_faults.py::test_corrupt_latest_checkpoint_falls_back",
    "test_faults.py::test_ckpt_corrupt_injection_and_fallback",
    # in-graph diagnostics (ISSUE 6): the whole file is quick-tier by
    # design — units, the sow/collect chain, trainer integration, the
    # nan-provenance end-to-end drive and the zero-recompile tripwire
    # all run on test-size models (satellite: regressions trip in
    # tier-1); plus the HLO byte-identity pin for diagnostics-off
    "test_diagnostics.py",
    "test_compiled_invariants.py::test_diag_off_hlo_byte_identical",
    # the served layout: the tick's plane leaves go straight
    # into their products, and the train step is the same program
    "test_compiled_invariants.py::test_served_tick_reads_plane_leaves",
    "test_compiled_invariants.py::test_train_step_is_unchanged_by_the_served",
    # paged KV cache (ISSUE 7): the whole file is quick-tier by design —
    # allocator/radix units, the bitwise paged-attention parity ladder
    # (ragged + block-boundary + trash-garbage), the Pallas pool-native
    # twin, paged-engine parity vs generate() (incl. int8, GQA/RoPE,
    # unrolled layers), prefix-reuse hits, chunked-prefill interleaving,
    # preempt-requeue bitwise continuity, the every-exit-path block-leak
    # invariant, the paged zero-recompile tripwire and the report CLI's
    # serving table — all on test-size models. The paged HLO pins ride
    # the already-quick test_serving_invariants parametrization.
    "test_paging.py",
    # speculative decoding (ISSUE 8): rejection-kernel units + the
    # chi-squared losslessness check, offline generate_speculative
    # bitwise parity (self-draft, truncated draft, int8, GQA/RoPE,
    # stop ids), and the serving engine's spec tick (greedy parity
    # incl. prefix hits + preemption, seeded determinism, zero
    # recompiles, telemetry columns) — all on test-size models. The
    # spec HLO pin rides test_serving_invariants.
    "test_spec.py::TestSpeculativeAccept",
    "test_spec.py::test_slot_filtered_probs_matches_sampler_distribution",
    "test_spec.py::test_offline_greedy_bitwise_gpt2",
    "test_spec.py::test_offline_greedy_bitwise_llama_gqa_rope",
    "test_spec.py::test_offline_greedy_bitwise_int8fwd",
    "test_spec.py::test_offline_greedy_bitwise_truncated_draft",
    "test_spec.py::test_offline_greedy_bitwise_stop_ids",
    "test_spec.py::test_offline_falls_back_when_context_tight",
    "test_spec.py::test_truncated_draft_validations",
    "test_spec.py::test_engine_spec_parity_greedy",
    # (engine_spec_parity_llama_and_int8 — ~26 s of llama+int8 spec
    # breadth — moved to the full tier for the 870 s budget; the greedy
    # /truncated-draft/preemption/int8fwd quick parities keep spec
    # decode pinned bitwise)
    "test_spec.py::test_engine_spec_parity_truncated_draft",
    "test_spec.py::test_engine_spec_prefix_hits_stay_bitwise",
    "test_spec.py::test_engine_spec_preemption_stays_bitwise",
    "test_spec.py::test_engine_spec_zero_recompiles_and_determinism",
    "test_spec.py::test_engine_spec_requires_paged",
    "test_spec.py::test_engine_spec_telemetry_rows",
    # learned drafting (ISSUE 16): the make_draft validation walls, the
    # engine swap refusal walls, the fleet-wide architecture refusal,
    # and the ISSUE-mandated in-process fleet broadcast (same-structure
    # tree swapped mid-stream on 2 replicas: bitwise vs generate(),
    # per-replica identity in summary/telemetry/report). Everything
    # that touches distill_corpus's teacher-generate compile or trains
    # — distill loss smoke, corpus determinism, offline bitwise
    # anchors, adaptive-k retrace tripwire, engine mid-stream swap,
    # checkpoint round-trip, the SUBPROCESS wire-op e2e and the example
    # run — stays full-suite-only: tier-1 sits within ~2% of its 870 s
    # budget, so quick-tier additions here are capped at the ~25 s the
    # fleet-swap anchor plus walls cost.
    "test_spec.py::test_make_draft_validations",
    "test_spec.py::test_engine_draft_hot_swap_refusals",
    "test_distill.py::test_router_inprocess_fleet_swap_midstream_bitwise",
    "test_distill.py::test_router_refuses_mismatched_draft_fleet_wide",
    # replica router chaos suite (ISSUE 9): fault-spec units, the
    # resume-from-tokens engine satellite, crash-mid-stream bitwise
    # parity (dense + paged), the hang watchdog bound, NaN quarantine +
    # rejoin, overload shedding, SIGTERM drain, zero recompiles across
    # a failover, seeded determinism across a failover, telemetry +
    # report table — all in-process on the shared test-size engine
    # geometry (the file rides test_serving/test_paging's compiles).
    # The SUBPROCESS-mode test (spawns jax-importing workers) stays
    # full-suite-only.
    "test_router.py::test_serving_fault_specs_parse_and_fire_once",
    "test_router.py::test_engine_resume_from_tokens_dense_and_paged",
    "test_router.py::test_engine_resume_seeded_sampling_continues_stream",
    "test_router.py::test_engine_health_snapshot_and_finite_probe",
    "test_router.py::test_crash_midstream_greedy_bitwise_dense",
    "test_router.py::test_crash_midstream_greedy_bitwise_paged",
    "test_router.py::test_retry_budget_exhausted_fails_request",
    "test_router.py::test_hang_detected_within_watchdog_bound",
    "test_router.py::test_nan_replica_quarantined_then_rejoins_after_warmup",
    "test_router.py::test_shed_under_overload_keeps_queue_bounded",
    "test_router.py::test_sigterm_drain_finishes_resident_streams_no_orphans",
    "test_router.py::test_zero_steadystate_recompiles_across_failover",
    "test_router.py::test_seeded_sampling_determinism_across_failover",
    "test_router.py::test_router_telemetry_rows_and_report_table",
    # elastic recovery (ISSUE 10): the replica-worker checkpoint key and
    # the in-process router auto-respawn trio, on the suite-shared
    # test-size geometry. The SUBPROCESS respawn e2e (spawns
    # jax-importing workers) stays full-tier-only.
    "test_respawn.py::test_worker_checkpoint_key_restores",
    "test_respawn.py::test_worker_checkpoint_absent_falls_back",
    "test_respawn.py::test_router_respawn_rejoins_and_serves",
    "test_respawn.py::test_router_respawn_budget_exhausts",
    "test_respawn.py::test_respawn_warmup_timeout_declares",
    # the one compile cache (ISSUE 32): a second process finds the
    # engine's tick and chunk, the Trainer's step and the toy dots3 tick
    # in JAX's persistent cache — no miss, bitwise the first's output
    "test_xla_cache.py",
    # prefill/decode disaggregation (ISSUE 12): FleetPrefixIndex +
    # radix local/remote-split units, the wire codec round-trip, the
    # KV export/import bitwise anchors (ragged block-boundary lengths,
    # seeded sampling, prefix-hit offset export), import validation
    # walls, the disagg router parity + both mid-handoff death
    # scenarios, deterministic fleet prefix shipping, the disagg
    # zero-recompile tripwire and the report columns — all in-process
    # on the suite-shared test-size geometry. The SUBPROCESS e2e
    # (spawns jax-importing workers) stays full-suite-only.
    "test_disagg.py::test_fleet_prefix_index_units",
    "test_disagg.py::test_radix_remote_split_and_frontier",
    "test_disagg.py::test_kv_payload_wire_roundtrip",
    "test_disagg.py::test_kv_roundtrip_bitwise_ragged_lengths",
    "test_disagg.py::test_kv_roundtrip_bitwise_seeded_sampling",
    "test_disagg.py::test_kv_export_after_prefix_hit_bitwise",
    "test_disagg.py::test_import_validation_walls",
    "test_disagg.py::test_disagg_router_bitwise_and_handoffs",
    "test_disagg.py::test_disagg_decode_death_after_import_is_lossless",
    "test_disagg.py::test_disagg_prefill_death_with_parked_streams_is_lossless",
    "test_disagg.py::test_fleet_prefix_steering_ships_blocks",
    # KV compression over the stream (ISSUE 13): compressed-block
    # handoff + rejection walls + int8 fleet shipping (the subprocess
    # int8 wire run stays full-suite-only with its bf16 sibling)
    "test_disagg.py::test_kv_roundtrip_int8_compressed_blocks",
    "test_disagg.py::test_import_rejects_dtype_and_version_mismatch",
    "test_disagg.py::test_fleet_prefix_ships_int8_blocks",
    "test_disagg.py::test_zero_recompiles_steady_state_disagg",
    "test_disagg.py::test_report_cli_renders_disagg_columns",
    # SLO-aware autoscaling + multi-tenant admission (ISSUE 15): the
    # traffic-generator determinism/shape units, the WDRR fairness and
    # per-tenant cap/rate properties (hot tenant at 10x cannot shed a
    # compliant one), the fake-clock autoscaler hysteresis/cooldown/
    # bounds/role-aware units against a stub router, the signal-ring
    # stats, the tombstoned add/remove lifecycle, the closed-loop
    # flash-crowd -> warm scale-up -> drain-down demo (zero fresh XLA
    # traces across joins), lossless tenant preemption, and the
    # per-request KV window override walls + bitwise anchor — all
    # in-process. The SUBPROCESS autoscale e2e stays full-tier-only.
    "test_autoscale.py::test_traffic_determinism_and_validation",
    "test_autoscale.py::test_traffic_shapes_tenant_mix_and_prefixes",
    "test_autoscale.py::test_wdrr_weighted_token_fairness_and_priority_tiers",
    "test_autoscale.py::test_admission_per_tenant_caps_and_rate_bucket",
    "test_autoscale.py::test_hot_tenant_at_10x_cannot_shed_compliant_tenant",
    "test_autoscale.py::test_pressure_clamps_kv_windows_by_priority",
    "test_autoscale.py::test_admission_deque_protocol_roundtrip",
    "test_autoscale.py::test_autoscaler_hysteresis_cooldown_and_bounds",
    "test_autoscale.py::test_autoscaler_role_aware_disagg_pools",
    "test_autoscale.py::test_signal_ring_bounded_stats_and_snapshot",
    "test_autoscale.py::test_router_add_remove_replica_tombstone_history",
    "test_autoscale.py::test_flash_crowd_autoscales_warm_and_drains_back",
    "test_autoscale.py::test_router_preempts_over_budget_tenant_losslessly",
    "test_autoscale.py::test_router_rejects_incompatible_kv_override_loudly",
    "test_autoscale.py::test_per_request_window_override_bitwise",
    "test_autoscale.py::test_kv_override_rejection_walls",
    "test_autoscale.py::test_engine_preempt_request_lossless_and_states",
    # distributed request tracing (ISSUE 17): context/wire units, the
    # critical-path exact-tiling sweep + TTFT clip, SLO-debt
    # attribution, chrome-lane tid coercion, the KV-payload origin/
    # trace carry, CLI + report tables, the in-process disagg fleet
    # e2e (handoff + injected failover, 100% connected chains, stage
    # sums tile the terminal latency) and the off-means-off pin (zero
    # recompiles, identical event streams). The SUBPROCESS wire e2e
    # (spawns jax-importing workers) stays full-suite-only.
    "test_tracing.py::test_trace_context_wire_roundtrip",
    "test_tracing.py::test_tracer_rows_and_clock_anchor",
    "test_tracing.py::test_critical_path_exact_tiling_and_ttft_clip",
    "test_tracing.py::test_slo_debt_attribution_and_tracer_ledger",
    "test_tracing.py::test_chrome_trace_lanes_and_tid_coercion",
    "test_tracing.py::test_kv_payload_wire_carries_origin_and_trace",
    "test_tracing.py::test_trace_cli_and_report_section",
    "test_tracing.py::test_fleet_trace_connected_across_handoff_and_failover",
    "test_tracing.py::test_tracing_off_is_off",
    # persistent sessions + tiered KV hierarchy (ISSUE 18): the store
    # tier/LRU/tenant-cap/corruption/CLI units and the FleetSessionIndex
    # + conversation-generator units are pure host work (<0.1 s); the
    # engine/router anchors (park/adopt/demote/store reattach bitwise,
    # kv_window wire carry, export/seed ship, all-tiers router flow +
    # restart, drain cross-replica reattach, conversation replay, int8
    # + seeded store round-trip) ride the suite-shared test-size
    # geometry and the programs test_paging/test_router/test_disagg
    # already compiled — ~25 s incremental, warm. The SUBPROCESS wire
    # e2e (spawns jax-importing workers) stays full-suite-only.
    "test_sessions.py::test_session_id_validation",
    "test_sessions.py::test_fleet_session_index_units",
    "test_sessions.py::test_store_lru_demotion_and_tenant_caps",
    "test_sessions.py::test_store_restart_corruption_torn_and_version",
    "test_sessions.py::test_store_cli_ls_verify_gc",
    "test_sessions.py::test_conversation_generator_determinism",
    "test_sessions.py::test_engine_and_router_session_walls",
    "test_sessions.py::test_engine_sessions_park_adopt_store_bitwise",
    "test_sessions.py::test_parked_sessions_never_deadlock_admission",
    "test_sessions.py::test_engine_sessions_seeded_and_int8_bitwise",
    "test_sessions.py::test_kv_window_override_rides_wire",
    "test_sessions.py::test_replica_ship_export_seed_bitwise",
    "test_sessions.py::test_router_sessions_all_tiers_bitwise",
    "test_sessions.py::test_router_cross_replica_reattach_when_owner_drains",
    "test_sessions.py::test_conversation_replay_drives_reattaches",
    # -- chaos soak (ISSUE 19): the rate-based fault grammar, the wire
    # manglers against a bare os.pipe, session-tier I/O faults, the
    # MTTR join, and the in-process mini-soak twin (seeded diurnal
    # trace + ChaosSchedule + live autoscaler + strict invariants) —
    # a few seconds warm, dominated by the mini-soak. The timeout-
    # ladder test (real sleeps) and the SUBPROCESS soak (real workers,
    # wall clock) stay full-suite-only: tier-1 has no slack for them.
    "test_chaos.py::test_chaos_grammar_rate_specs_parse_and_walls",
    "test_chaos.py::test_chaos_schedule_deterministic_and_targeted",
    "test_chaos.py::test_mangle_recv_wire_kinds",
    "test_chaos.py::test_torn_wire_line_is_protocol_fault_not_crash",
    "test_chaos.py::test_wire_drop_keeps_op_pending",
    "test_chaos.py::test_session_store_io_faults_absorbed_and_fallback",
    "test_chaos.py::test_autoscaler_holds_scaledown_while_degraded",
    "test_chaos.py::test_recovery_table_and_report_section",
    "test_chaos.py::test_mini_soak_invariants_and_fairness_under_chaos",
    # bring-up tripwires (ISSUE 21): the whole file — cross-lowering for
    # the TPU on the CPU (the paged kernel's block specs, flash under a
    # 4-device mesh, tp at vocab 50,257, the engine's default tick) and
    # the no-hidden-fallback walls (no backend on import, one process
    # per chip, --backend tpu / chip_smoke.py refusing a CPU, the
    # cache's one placement rule), ~15 s together, and the serve
    # programs' pool-copy tripwire (ISSUE 28: the tick and the chunk,
    # scanned and unrolled, compiled for a v5e at gpt2-medium width with
    # two layers, ~20 s each). Its other full-width compiles against the
    # v5e topology carry their own `slow` mark.
    "test_tpu_lowering.py",
    # the one host-span instrument (ISSUE 27): the ring's parent / ids /
    # snapshot / profiler annotation / host/gc units, and the serve/*,
    # train/* spans plus the queue-wait counters of an engine, a router
    # and a Trainer built with NO telemetry directory (~20 s together:
    # two test-size engines and one router compile)
    "test_host_spans.py",
)


def pytest_collection_modifyitems(items):
    """`-m quick` = the allowlist above; everything else is marked slow.
    `pytest tests/` (no -m) remains the full suite."""
    import pytest

    for item in items:
        if any(s in item.nodeid for s in _QUICK):
            item.add_marker(pytest.mark.quick)
        else:
            item.add_marker(pytest.mark.slow)


_EXIT_STATUS = [None]


def pytest_sessionfinish(session, exitstatus):
    _EXIT_STATUS[0] = int(exitstatus)


def pytest_unconfigure(config):
    # Interpreter teardown after a full tier-1 run costs ~30 s: GC and
    # XLA-client destructors walk hundreds of compiled executables and
    # device arrays accumulated across ~330 tests, after every test has
    # already passed or failed. That dead time counts against the
    # tier-1 wall-clock budget, so skip it: once the terminal summary
    # is out, flush and exit with the session's real status. (No
    # coverage/teardown-dependent plugins are in play; pytest's tmp
    # dirs are reaped lazily by later runs.)
    if _EXIT_STATUS[0] is not None:
        import os as _os
        import sys as _sys

        _sys.stdout.flush()
        _sys.stderr.flush()
        _os._exit(_EXIT_STATUS[0])
