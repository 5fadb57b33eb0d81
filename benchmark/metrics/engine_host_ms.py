"""Host time of an engine step: the program's `serve/engine_step` span
less its two waits for the device (`serve/tick_sync`,
`serve/prefill_sync`), mean over every engine step of the window (the
span ring)."""

from benchmark import ringread


def read(ctx):
    return ringread.mean_less_ms(ringread.window_spans(ctx),
                                 "serve/engine_step",
                                 ("serve/tick_sync", "serve/prefill_sync"))
