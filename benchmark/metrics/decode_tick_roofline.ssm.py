"""The decode tick of a model with state-space layers against its
roofline: the least time the chip could take for the ticks of the traced
window, `max(bytes / HBM, FLOPs / bf16 peak)`, over the time they took.
What a tick has to move is counted by the family (`decode_tick_bytes`):
the weights once, each live stream's state of every mamba layer read and
written back (the window's mean of the engine's `ssm_states_read` plus
`ssm_states_written` a tick, `family.state_bytes` each), and each live
stream's attention rows, the whole context in every attention layer."""


def read(ctx):
    eng, fam = ctx.counters["engine"], ctx.family
    read_, written = (eng.get("ssm_states_read"),
                      eng.get("ssm_states_written"))
    ticks = eng.get("ticks")
    runs = ctx.trace.program_runs(ctx.mix["programs"]["tick"])
    if (read_ is None or written is None or not ticks or not runs
            or ctx.trace_span is None or not hasattr(fam, "state_bytes")):
        return None
    lo, hi = ctx.trace_span
    contexts = [r.prompt_len + j for r in ctx.records
                for j, t in enumerate(r.token_times)
                if j >= 1 and lo <= t < hi]
    if not contexts:
        return None
    # every tick reads the weights and moves its live streams' states;
    # a stream's rows are read once a delivered token
    nbytes = (fam.decode_tick_bytes(ctx.config, contexts,
                                    len(runs) * (read_ + written) / ticks)
              + (len(runs) - 1) * fam.decode_weight_bytes(ctx.config))
    flops = sum(fam.forward_flops_token(ctx.config, c, head=True)
                for c in contexts)
    least = max(nbytes / ctx.peaks.hbm_bytes_per_s,
                flops / ctx.peaks.bf16_flops)
    return 100.0 * least / (sum(r.dur for r in runs) / 1e9)
