"""Host time of a `router.step` span that no program it launched covers:
the span's length less the device time of the programs that ran inside
it, mean over the traced window's spans."""


def read(ctx):
    lo, hi = ctx.trace.window
    spans = [e for e in ctx.trace.host
             if e.name == "router.step" and e.start >= lo and e.end <= hi]
    if not spans:
        return None
    runs = ctx.trace.devices[0].modules
    host = 0.0
    for s in spans:
        dev = sum(min(r.end, s.end) - max(r.start, s.start) for r in runs
                  if r.end > s.start and r.start < s.end)
        host += max(0.0, s.dur - dev)
    return host / len(spans) / 1e6
