"""Host time to put a decode tick's operands on the device (the pools'
tables, lengths, tokens, key data, counts, the three sampling vectors):
mean length of the program's `serve/tick_operands` spans in the window.
With `tick_call_host_ms` it is `tick_dispatch_host_ms`."""

from benchmark import ringread


def read(ctx):
    return ringread.mean_ms(ringread.window_spans(ctx),
                            "serve/tick_operands")
