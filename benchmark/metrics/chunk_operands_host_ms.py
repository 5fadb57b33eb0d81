"""Host time to build a prefill chunk's operands and put them on the
device (the chunk's tokens, its position, a table row a pool, the key
data, four scalars): mean length of the program's `serve/chunk_operands`
spans in the window. `chunk_host_ms` less this and less a windowed pool's
`serve/grow_slots` is the call (`serve/chunk_call`)."""

from benchmark import ringread


def read(ctx):
    return ringread.mean_ms(ringread.window_spans(ctx),
                            "serve/chunk_operands")
