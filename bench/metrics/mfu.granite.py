"""Per-layer metric `mfu.granite`: the mixed stack's whole decode step's
share of the chip's peak FLOP/s: model FLOPs of the tokens the window's
decode steps delivered (`bench.work_hybrid.decode_token_flops`) over those
steps' wall time times the peak."""
from bench import work_hybrid


def read(ctx):
    steps, contexts = work_hybrid.delivered_contexts(ctx)
    if not steps or not contexts:
        return None
    flops = sum(work_hybrid.decode_token_flops(ctx["arch"], c)
                for c in contexts)
    wall = sum(s.t1 - s.t0 for s in steps)
    return 100.0 * flops / (wall * ctx["peaks"]["bf16_flops_per_s"])
