"""The experts' grouped products against their roofline: the least time
the chip could take for the `expert_banks` kernel's work in the traced
window's ticks and chunks, over the device time of the operations named
`expert_banks` inside them.

The work is counted from the engine's device counters (`summary()`),
which cover the whole window: a tick's `moe_experts_hit` (distinct held
experts its live tokens chose, summed over the layers) and
`moe_assignments_held`, and a chunk's the same under `chunk_` names.
A chunk's are its window means times the traced chunks. A tick's follow
the traced ticks' own live tokens (the tokens delivered in the traced
seconds, from the records), against the window's (`slot_occupancy`):
assignments in proportion, experts hit in proportion where the traced
ticks are less full and at the window's mean where they are fuller (the
experts a token set hits grow more slowly than the set), so that the
estimate lies under the traced ticks' own count. Bytes: each expert hit
read once (`family.expert_bytes`) and each held assignment's row in and
out (the bf16 row into gate and up, the bf16 hidden row into down, the
float32 results of the three); FLOPs: 6 x width x expert width an
assignment. The least time is the larger of the two at the chip's peaks.
A free slot's or a padded position's token is multiplied and not
counted, so the share reads the kernel low, never high. `None` where the
counters are missing (a program that does not count a chunk's experts)
or the trace holds no such kernel."""


def read(ctx):
    eng, fam, cfg = ctx.counters["engine"], ctx.family, ctx.config
    ticks, chunks = eng.get("ticks"), eng.get("prefill_chunks")
    keys = ("moe_experts_hit", "moe_assignments_held", "slot_occupancy",
            "chunk_moe_experts_hit", "chunk_moe_assignments_held")
    if (not ticks or not chunks or any(eng.get(k) is None for k in keys)
            or not hasattr(fam, "expert_params")
            or ctx.trace_span is None):
        return None
    tick_runs = ctx.trace.program_runs(ctx.mix["programs"]["tick"])
    chunk_runs = ctx.trace.program_runs(ctx.mix["programs"]["chunk"])
    seconds = ctx.trace.scope_time("expert_banks", tick_runs + chunk_runs)
    if not seconds:
        return None
    lo, hi = ctx.trace_span
    # tokens the traced ticks delivered, over what the window's ticks
    # delivered a tick
    delivered = sum(1 for r in ctx.records
                    for j, t in enumerate(r.token_times)
                    if j >= 1 and lo <= t < hi)
    live = eng["slot_occupancy"] * ctx.mix["engine"]["num_slots"]
    fill = (delivered / (len(tick_runs) * live)
            if tick_runs and live else 0.0)
    d = cfg["hidden_size"]
    f = fam.expert_params(cfg) // (3 * d)
    per_row = 2 * (2 * d + f) + 4 * (2 * f + d)
    hit = (len(tick_runs) * eng["moe_experts_hit"] / ticks * min(1.0, fill)
           + len(chunk_runs) * eng["chunk_moe_experts_hit"] / chunks)
    rows = (len(tick_runs) * eng["moe_assignments_held"] / ticks * fill
            + len(chunk_runs) * eng["chunk_moe_assignments_held"] / chunks)
    nbytes = hit * fam.expert_bytes(cfg) + rows * per_row
    flops = rows * 2 * fam.expert_params(cfg)
    least = max(nbytes / ctx.peaks.hbm_bytes_per_s,
                flops / ctx.peaks.bf16_flops)
    return 100.0 * least / seconds
