"""Host time to build a decode tick's operands and dispatch it: mean
length of the program's `serve/tick_dispatch` spans in the window."""

from benchmark import ringread


def read(ctx):
    return ringread.mean_ms(ringread.window_spans(ctx),
                            "serve/tick_dispatch")
