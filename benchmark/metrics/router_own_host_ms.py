"""Host time of the router's own phases a step: the program's
`serve/router_step` span less the `serve/engine_step` spans under it,
mean over every router step of the window (the span ring)."""

from benchmark import ringread


def read(ctx):
    return ringread.mean_less_ms(ringread.window_spans(ctx),
                                 "serve/router_step",
                                 ("serve/engine_step",))
