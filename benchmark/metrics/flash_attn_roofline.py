"""Training's attention against the compute roofline: causal attention
FLOPs forward and backward of the traced window's steps, counted from
shapes, over peak, over the device time of the operations named after
the attention scope (one chip's share of both)."""


def read(ctx):
    runs = ctx.trace.program_runs(ctx.mix["programs"]["step"])
    seconds = ctx.trace.scope_time(ctx.mix["attention_scope"], runs)
    if not runs or seconds == 0:
        return None
    flops = (len(runs) * ctx.rows / ctx.chips
             * ctx.family.train_attention_flops_per_seq(ctx.config,
                                                    ctx.seq_len))
    return 100.0 * flops / ctx.peaks.bf16_flops / seconds
