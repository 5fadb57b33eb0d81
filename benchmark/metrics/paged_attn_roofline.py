"""The decode attention alone against the memory roofline: bytes of the
live keys and values of the traced window's decode tokens over peak
bandwidth, over the device time of the operations named after the
attention scope inside the tick program."""

from benchmark import served


def read(ctx):
    work = served.decode_work(ctx)
    if work is None:
        return None
    runs = ctx.trace.program_runs(ctx.mix["programs"]["tick"])
    seconds = ctx.trace.scope_time(ctx.mix["attention_scope"], runs)
    if seconds == 0:
        return None
    return 100.0 * work[3] / ctx.peaks.hbm_bytes_per_s / seconds
