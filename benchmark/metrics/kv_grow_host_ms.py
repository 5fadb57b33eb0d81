"""Host time to back every live slot's next write with a block: mean
length of the program's `serve/grow_slots` spans in the window."""

from benchmark import ringread


def read(ctx):
    return ringread.mean_ms(ringread.window_spans(ctx), "serve/grow_slots")
