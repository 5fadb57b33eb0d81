"""Positions the full layers attend over the positions live in them,
over the window's ticks, in percent: what the learned selection leaves of
the cache. From the engine's `summary()` (`sparse_selected`,
`sparse_live`)."""


def read(ctx):
    eng = ctx.counters["engine"]
    sel, live = eng.get("sparse_selected"), eng.get("sparse_live")
    return 100.0 * sel / live if sel is not None and live else None
