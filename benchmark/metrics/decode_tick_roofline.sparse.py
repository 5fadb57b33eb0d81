"""The decode tick of a model whose cache is read sparsely, against its
roofline: the least time the chip could take for the ticks of the traced
window, over the time they took. What a tick has to read is counted by
the family (`decode_tick_bytes`): the weights outside the routed experts
once, the held experts that a live token chose (the window's mean of the
engine's `moe_experts_hit` a tick, never all of them by default), and for
each live stream the indexer's keys of every position, the rows attended
in each full layer and the window's rows in each sliding layer."""


def read(ctx):
    eng = ctx.counters["engine"]
    hit, ticks = eng.get("moe_experts_hit"), eng.get("ticks")
    runs = ctx.trace.program_runs(ctx.mix["programs"]["tick"])
    if hit is None or not ticks or not runs or ctx.trace_span is None:
        return None
    lo, hi = ctx.trace_span
    contexts = [r.prompt_len + j for r in ctx.records
                for j, t in enumerate(r.token_times)
                if j >= 1 and lo <= t < hi]
    if not contexts:
        return None
    fam = ctx.family
    # every tick reads the weights; the streams' rows are read once a
    # delivered token
    nbytes = (fam.decode_tick_bytes(ctx.config, contexts, 0.0)
              + (len(runs) - 1) * fam.dense_weight_bytes(ctx.config)
              + len(runs) * hit / ticks * fam.expert_bytes(ctx.config))
    flops = sum(fam.forward_flops_token(ctx.config, c, head=True)
                for c in contexts)
    least = max(nbytes / ctx.peaks.hbm_bytes_per_s,
                flops / ctx.peaks.bf16_flops)
    return 100.0 * least / (sum(r.dur for r in runs) / 1e9)
