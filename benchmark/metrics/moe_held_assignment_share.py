"""Assignments of live tokens to the experts this chip holds, over all
their assignments, over the window's ticks, in percent; an eighth where
32 of 256 experts are held and routing is even. From the engine's
`summary()` (`moe_assignments_held`, `moe_assignments_total`)."""


def read(ctx):
    eng = ctx.counters["engine"]
    held, total = (eng.get("moe_assignments_held"),
                   eng.get("moe_assignments_total"))
    return 100.0 * held / total if held is not None and total else None
