"""Host time of a prefill chunk: the program's `serve/prefill` span (a
windowed pool's backing, the chunk's build and puts, the call) less the
wait for the first token under it (`serve/prefill_sync`), mean over every
chunk of the window (the span ring)."""

from benchmark import ringread


def read(ctx):
    return ringread.mean_less_ms(ringread.window_spans(ctx),
                                 "serve/prefill", ("serve/prefill_sync",))
