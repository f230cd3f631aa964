"""Per-layer metric `decode_tick_ms`: see `bench.readers.decode_tick_ms`."""
from bench import readers


def read(ctx):
    return readers.decode_tick_ms(ctx)
