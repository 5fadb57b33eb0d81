"""What a params-finite probe costs the step that runs it: mean length
of the program's `serve/probe` spans in the window, the dispatch and the
device's read together, since the step waits for both."""

from benchmark import ringread


def read(ctx):
    return ringread.mean_ms(ringread.window_spans(ctx), "serve/probe")
