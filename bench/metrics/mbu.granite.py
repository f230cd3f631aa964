"""Per-layer metric `mbu.granite`: the mixed stack's decode step's share
of its HBM roofline: the least bytes of the window's decode ticks (the
weights read once a tick, and for each live slot only its SSM state and
conv tail read and written and its KV rows read,
`bench.work_hybrid`) over the decode steps' wall time times the chip's
HBM bytes per second. Idle slots are never counted."""
from bench import work_hybrid


def read(ctx):
    steps, contexts = work_hybrid.delivered_contexts(ctx)
    if not steps or not contexts:
        return None
    arch = ctx["arch"]
    ticks = sum(s.decode_ticks for s in steps)
    nbytes = (ticks * work_hybrid.weight_bytes(arch)
              + sum(work_hybrid.decode_token_bytes(arch, c)
                    for c in contexts))
    wall = sum(s.t1 - s.t0 for s in steps)
    return 100.0 * nbytes / (wall * ctx["peaks"]["hbm_bytes_per_s"])
