"""The decode tick against its roofline: the least time the chip could
take for the ticks of the traced window, over the time they took. The
least time is the larger of FLOPs over peak and bytes over bandwidth,
with the weights read once a tick and the live keys and values of every
stream once, all counted from shapes."""

from benchmark import served


def read(ctx):
    work = served.decode_work(ctx)
    if work is None:
        return None
    seconds, nbytes, flops, _ = work
    least = max(nbytes / ctx.peaks.hbm_bytes_per_s,
                flops / ctx.peaks.bf16_flops)
    return 100.0 * least / seconds
