"""Per-layer metric `decode_step_roofline`: the SLAY decode kernel's share
of its roofline; see `bench.readers.decode_step_roofline`."""
from bench import readers


def read(ctx):
    return readers.decode_step_roofline(ctx)
