"""Per-layer metric `decode_tick_ms.granite`: the mixed stack's decode
tick; see `bench.readers.decode_tick_ms`."""
from bench import readers


def read(ctx):
    return readers.decode_tick_ms(ctx)
