"""One sharded-pool serving-bench row.

:func:`sharded_row` replays the *same* seeded Poisson trace as the
bench's constant_state row on a mesh=(data=N,) slot-sharded pool; the
bench asserts its ``stream_digest`` equals the single-shard row's — the
DESIGN.md §8 byte-identical-stream contract.

On an accelerator the bench calls :func:`sharded_row` in its own process,
on the devices present: a process that holds the chips cannot hand them to
a child. On CPU, jax pins the device count at first init, so the bench
runs this module as a child with
``XLA_FLAGS=--xla_force_host_platform_device_count=<data>``; it prints
the row as JSON on stdout.
"""
from __future__ import annotations

import argparse
import json
import sys


def sharded_row(*, load: float, n: int, max_new: int, prompt: tuple,
                num_slots: int, max_len: int, prefill_chunk: int,
                macro_ticks: int, data: int) -> dict:
    import jax
    import numpy as np

    from benchmarks.serving_bench import _poisson_trace, _stream_digest
    from repro import configs
    from repro.configs.base import ServingConfig
    from repro.launch.mesh import make_serving_mesh
    from repro.models import api
    from repro.serving.engine import ContinuousServingEngine

    cfg = configs.get_smoke_config("slayformer-124m", attn_kind="slay")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1234)
    reqs = _poisson_trace(rng, n, load, prompt, cfg.vocab_size, max_new)
    eng = ContinuousServingEngine(
        cfg, params, make_serving_mesh(data),
        serving=ServingConfig(num_slots=num_slots, max_len=max_len,
                              prefill_chunk=prefill_chunk,
                              macro_ticks=macro_ticks, slot_shards=data))
    outs, summary = eng.run(reqs)
    assert summary["requests_completed"] == n
    assert summary["slot_shards"] == data, summary["slot_shards"]
    return {"regime": "constant_state_sharded", "load": load,
            "num_slots": num_slots, "requests": n,
            "mesh_devices": jax.device_count(),
            "stream_digest": _stream_digest(outs),
            "jit_cache_entries": eng.jit_cache_entries(), **summary}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--load", type=float, required=True)
    ap.add_argument("--n", type=int, required=True)
    ap.add_argument("--max-new", type=int, required=True)
    ap.add_argument("--prompt-lo", type=int, required=True)
    ap.add_argument("--prompt-hi", type=int, required=True)
    ap.add_argument("--num-slots", type=int, required=True)
    ap.add_argument("--max-len", type=int, required=True)
    ap.add_argument("--prefill-chunk", type=int, required=True)
    ap.add_argument("--macro-ticks", type=int, required=True)
    ap.add_argument("--data", type=int, required=True)
    args = ap.parse_args()
    row = sharded_row(load=args.load, n=args.n, max_new=args.max_new,
                      prompt=(args.prompt_lo, args.prompt_hi),
                      num_slots=args.num_slots, max_len=args.max_len,
                      prefill_chunk=args.prefill_chunk,
                      macro_ticks=args.macro_ticks, data=args.data)
    json.dump(row, sys.stdout)
    sys.stdout.write("\n")


if __name__ == "__main__":
    main()
