"""Per-layer metric `prefill_phase_ms.granite`: admission to first token
on the mixed stack; see `bench.readers.prefill_phase_ms`."""
from bench import readers


def read(ctx):
    return readers.prefill_phase_ms(ctx)
