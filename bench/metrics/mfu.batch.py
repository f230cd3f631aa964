"""Per-layer metric `mfu.batch`: the whole decode step's share of the chip's
peak FLOP/s; see `bench.readers.mfu_decode`."""
from bench import readers


def read(ctx):
    return readers.mfu_decode(ctx)
