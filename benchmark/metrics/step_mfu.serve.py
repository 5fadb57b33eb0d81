"""The served model's share of the chip's peak: forward FLOPs of every
prompt and answer token whose result was delivered inside the traced
window, attention over its real context, over window x peak. A prompt
counts when its first token is delivered."""

from benchmark import served


def read(ctx):
    if ctx.trace_span is None:
        return None
    lo, hi = ctx.trace_span
    flops = served.served_flops(ctx, lo, hi)
    if flops == 0:
        return None
    return 100.0 * flops / (hi - lo) / ctx.peaks.bf16_flops
