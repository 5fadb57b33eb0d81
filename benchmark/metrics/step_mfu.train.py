"""The whole step's share of the chips' peak: steps executed in the
traced window times tokens a step times FLOPs a trained token (counted
from shapes, recomputation not counted), over window x chips x peak."""


def read(ctx):
    runs = ctx.trace.program_runs(ctx.mix["programs"]["step"])
    if not runs:
        return None
    # from the first step's start to the last one's end: whole steps
    span = (max(r.end for r in runs) - min(r.start for r in runs)) / 1e9
    flops = (len(runs) * ctx.tokens_per_step
             * ctx.family.train_flops_per_token(ctx.config, ctx.seq_len))
    return 100.0 * flops / span / (ctx.chips * ctx.peaks.bf16_flops)
