"""Host time to hand a tick's tokens to their requests (callbacks and
retirements): mean length of the program's `serve/deliver` spans in the
window."""

from benchmark import ringread


def read(ctx):
    return ringread.mean_ms(ringread.window_spans(ctx), "serve/deliver")
