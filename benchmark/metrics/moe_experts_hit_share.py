"""Distinct experts that a tick's live tokens chose, over the experts of
all layers (`family.routed_experts`: 64 a layer), mean over the window's
ticks, in percent: the share of the experts' weights a tick has to read.
From the engine's `summary()` (`moe_experts_hit`, summed over the layers
on the device and over the ticks on the host; `ticks`)."""


def read(ctx):
    eng, fam = ctx.counters["engine"], ctx.family
    hit, ticks = eng.get("moe_experts_hit"), eng.get("ticks")
    if not hit or not ticks or not hasattr(fam, "routed_experts"):
        return None
    return 100.0 * hit / (ticks * fam.routed_experts(ctx.config))
