"""Per-layer metric `queue_wait_ms`: see `bench.readers.queue_wait_ms`."""
from bench import readers


def read(ctx):
    return readers.queue_wait_ms(ctx)
