"""The selective-scan kernel's share of the device's busy time in the
traced window, in percent: how much of the work the state-space layers'
recurrence does (the mixers' projections and the tick's one elementwise
step are not in it; the `ssm_scan` kernel of the chunks is)."""


def read(ctx):
    seconds = ctx.trace.scope_time(ctx.mix.get("scan_scope", "ssm_scan"))
    if not seconds:
        return None
    return 100.0 * seconds / ctx.trace.busy_s()
