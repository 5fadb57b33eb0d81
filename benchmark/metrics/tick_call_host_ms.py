"""Host time of the call of the jitted tick up to its return (argument
flattening, the static arguments' hashes, the cache lookup, the
enqueue): mean length of the program's `serve/tick_call` spans in the
window."""

from benchmark import ringread


def read(ctx):
    return ringread.mean_ms(ringread.window_spans(ctx), "serve/tick_call")
