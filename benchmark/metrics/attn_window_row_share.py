"""Rows of the window layers' pool over all rows the live streams'
queries attended, over the window's ticks and the layers, in percent: 75
where every stream is shorter than the window (three window layers to one
full layer), less the further the streams run past it. From the engine's
`summary()` (`attn_window_rows`, `attn_full_rows`: the masks' own counts,
summed on the device)."""


def read(ctx):
    eng = ctx.counters["engine"]
    win, full = eng.get("attn_window_rows"), eng.get("attn_full_rows")
    if not win or full is None:
        return None
    return 100.0 * win / (win + full)
