"""Summary rows over all rows the live streams' queries attended, over
the window's ticks, in percent: how much of what a tick reads of the
cache stands for finished windows, a row a chunk. From the engine's
`summary()` (`eva_summary_rows`, `eva_window_rows`: the masks' own
counts, summed on the device)."""


def read(ctx):
    eng = ctx.counters["engine"]
    summ, win = eng.get("eva_summary_rows"), eng.get("eva_window_rows")
    if summ is None or win is None or not summ + win:
        return None
    return 100.0 * summ / (summ + win)
