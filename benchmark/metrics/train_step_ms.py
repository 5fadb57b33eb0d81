"""Device time of the train-step program per execution, median over the
traced window."""

import statistics


def read(ctx):
    runs = ctx.trace.program_runs(ctx.mix["programs"]["step"])
    if not runs:
        return None
    return statistics.median(r.dur for r in runs) / 1e6
