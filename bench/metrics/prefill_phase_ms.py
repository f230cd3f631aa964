"""Per-layer metric `prefill_phase_ms`: see `bench.readers.prefill_phase_ms`."""
from bench import readers


def read(ctx):
    return readers.prefill_phase_ms(ctx)
