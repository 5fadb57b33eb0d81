"""The decode attention of a model whose layers read two extents, against
the memory roofline: the key and value rows the traced window's decode
tokens had to read (`family.attended_bytes`: a full layer the whole
context, a window layer the last `window` positions of it, grouped heads'
rows once) over peak bandwidth, over the device time of the operations
named after the attention scope inside the tick program. That scope holds
the layer's projections beside the paged kernel, so the share reads the
kernel low, never high."""


def read(ctx):
    fam = ctx.family
    runs = ctx.trace.program_runs(ctx.mix["programs"]["tick"])
    if (not hasattr(fam, "attended_bytes") or not runs
            or ctx.trace_span is None):
        return None
    lo, hi = ctx.trace_span
    contexts = [r.prompt_len + j for r in ctx.records
                for j, t in enumerate(r.token_times)
                if j >= 1 and lo <= t < hi]
    seconds = ctx.trace.scope_time(ctx.mix["attention_scope"], runs)
    if not contexts or not seconds:
        return None
    return (100.0 * fam.attended_bytes(ctx.config, contexts)
            / ctx.peaks.hbm_bytes_per_s / seconds)
