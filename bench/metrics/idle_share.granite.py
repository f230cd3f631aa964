"""Per-layer metric `idle_share.granite`: share of the traced window with
no op on the device; see `bench.readers.idle_share`."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx)
