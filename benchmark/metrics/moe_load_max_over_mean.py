"""Tokens at the busiest held expert over the mean of the held experts,
over the window's ticks and the expert layers; from the device-side
counters the engine's `summary()` sums (`moe_load_max`,
`moe_load_mean`). 1 is even routing."""


def read(ctx):
    eng = ctx.counters["engine"]
    top, mean = eng.get("moe_load_max"), eng.get("moe_load_mean")
    return top / mean if top is not None and mean else None
