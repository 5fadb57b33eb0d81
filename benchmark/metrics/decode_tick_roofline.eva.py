"""The decode tick of a model that attends a window's rows beside the
summaries of finished windows, against its roofline: the least time the
chip could take for the ticks of the traced window, over the time they
took. What a tick has to move is counted by the family from shapes
(`decode_tick_bytes`: the weights once a tick and, a live stream a layer,
the window rows and summary rows its query attends and the one summary
row it writes where it fills a chunk), so that a later kernel is read
against the same count. The least time is the larger of those bytes over
the bandwidth and the FLOPs over the peak."""


def read(ctx):
    fam = ctx.family
    runs = ctx.trace.program_runs(ctx.mix["programs"]["tick"])
    if (not hasattr(fam, "decode_tick_bytes") or not runs
            or ctx.trace_span is None):
        return None
    lo, hi = ctx.trace_span
    contexts = [r.prompt_len + j for r in ctx.records
                for j, t in enumerate(r.token_times)
                if j >= 1 and lo <= t < hi]
    if not contexts:
        return None
    # every tick reads the weights; a stream's rows are read once a
    # delivered token
    nbytes = (fam.decode_tick_bytes(ctx.config, contexts)
              + (len(runs) - 1) * fam.decode_weight_bytes(ctx.config))
    flops = sum(fam.forward_flops_token(ctx.config, c, head=True)
                for c in contexts)
    least = max(nbytes / ctx.peaks.hbm_bytes_per_s,
                flops / ctx.peaks.bf16_flops)
    return 100.0 * least / (sum(r.dur for r in runs) / 1e9)
