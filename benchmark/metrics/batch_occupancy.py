"""Live slots per decode tick over `num_slots`, mean over the window's
ticks, in percent; from the engine's `summary()`."""


def read(ctx):
    v = ctx.counters["engine"].get("slot_occupancy")
    return None if v is None else 100.0 * v
