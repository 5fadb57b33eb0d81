"""Device time of the prefill-chunk program per execution, mean over the
traced window."""


def read(ctx):
    runs = ctx.trace.program_runs(ctx.mix["programs"]["chunk"])
    if not runs:
        return None
    return sum(r.dur for r in runs) / len(runs) / 1e6
