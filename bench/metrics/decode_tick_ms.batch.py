"""Per-layer metric `decode_tick_ms.batch`: the closed-loop cell's decode
tick (moves its tokens per second); see `bench.readers.decode_tick_ms`."""
from bench import readers


def read(ctx):
    return readers.decode_tick_ms(ctx)
