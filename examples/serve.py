"""Serving demo: continuous batching (slot pool, staggered arrivals,
streaming) or the lockstep reference, with SLAY's constant-size recurrent
state (no KV cache growth).

    PYTHONPATH=src python examples/serve.py                  # continuous
    PYTHONPATH=src python examples/serve.py --lockstep
    PYTHONPATH=src python examples/serve.py --arch phi4-mini-3.8b
    PYTHONPATH=src python examples/serve.py --no-smoke    # published widths

Sharded slot pool (DESIGN.md §8) — on CPU, force a multi-device runtime
first (jax pins its device count at first init):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/serve.py --slot-shards 4 --slots 4

The token streams printed are byte-identical to the unsharded run: the
sampler is keyed on (seed, rid, token-index), never on slot or shard
placement.

Paged slot memory + prefix cache (DESIGN.md §11) — page the KV rings so
short requests pin only the pages they need, and reuse shared prompt
prefixes across requests by state-snapshot copy; both preserve stream
byte-identity:

    PYTHONPATH=src python examples/serve.py --attn-kind softmax \\
        --page-size 16 --prefix-cache 64

Speculative decoding (DESIGN.md §13) — the linear SLAY regime drafts
gamma tokens per slot, the exact verifier scores them in one chunked
dispatch, and the accept/resample correction keeps the emitted streams
byte-identical to plain exact decode at temperature 0 (and exactly
verifier-distributed when sampled):

    PYTHONPATH=src python examples/serve.py --speculative --spec-gamma 2
"""
import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.configs.base import ServingConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_serving_mesh
from repro.models import api
from repro.serving.engine import (AdmissionError, ContinuousServingEngine,
                                  Request, ServingEngine)
from repro.serving.prefix_cache import PrefixCache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="slayformer-124m",
                    choices=list(configs.ALL_ARCHS))
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced widths (default); --no-smoke serves the "
                         "arch at its published widths")
    ap.add_argument("--attn-kind", default=None)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lockstep", action="store_true",
                    help="lockstep reference instead of continuous batching")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--slot-shards", type=int, default=0,
                    help="shard the slot pool N-way over the mesh `data` "
                         "axis (DESIGN.md §8); needs >= N devices")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound the admission queue (0 = unbounded); with "
                         "the default reject_new policy, overflow raises a "
                         "typed AdmissionError this demo catches")
    ap.add_argument("--overload-policy", default="reject_new",
                    choices=("reject_new", "shed_oldest", "queue_wait"))
    ap.add_argument("--page-size", type=int, default=0,
                    help="page the pooled KV rings into fixed-size pages "
                         "(DESIGN.md §11); 0 = unpaged. Ignored for "
                         "constant-state kinds (nothing to page)")
    ap.add_argument("--prefix-cache", type=int, default=0, metavar="MB",
                    help="content-addressed prompt-prefix cache budget in "
                         "MB (DESIGN.md §11); 0 = off. Repeated/shared "
                         "prompt prefixes seed their slot from a stored "
                         "snapshot instead of re-prefilling")
    ap.add_argument("--speculative", action="store_true",
                    help="draft-verify decode (DESIGN.md §13): linear SLAY "
                         "drafts, the exact verifier scores gamma+1 tokens "
                         "per dispatch. Needs an exact attn kind; defaults "
                         "the verifier to yat_spherical if --attn-kind is "
                         "not given")
    ap.add_argument("--spec-gamma", type=int, default=2,
                    help="draft tokens per speculative round")
    args = ap.parse_args()
    if args.speculative and args.prefix_cache:
        ap.error("--speculative and --prefix-cache are mutually exclusive "
                 "(DESIGN.md §13)")
    if args.speculative and args.lockstep:
        ap.error("--speculative needs the continuous engine")

    enable_compile_cache()
    overrides = {"attn_kind": args.attn_kind} if args.attn_kind else {}
    if args.speculative and not args.attn_kind:
        # Exact verifier + a deliberately small SLAY draft trunk so the
        # demo's draft steps stay cheap on CPU.
        overrides = {"attn_kind": "yat_spherical",
                     "slay_anchors": 16, "slay_prf": 32}
    get = configs.get_smoke_config if args.smoke else configs.get_config
    cfg = get(args.arch, **overrides)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    # DESIGN §8 walkthrough, step 1 — the mesh: the `data` axis carries
    # slot parallelism at serving time. make_serving_mesh(N) takes the
    # first N devices; with N=1 this is the plain host mesh.
    if args.slot_shards > 1:
        mesh = make_serving_mesh(args.slot_shards)
    else:
        mesh = make_host_mesh()

    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(3, cfg.vocab_size,
                                 size=rng.integers(4, 12)).astype(np.int32),
                    max_new_tokens=args.max_new,
                    arrival_time=float(2 * i))
            for i in range(args.batch)]
    print(f"serving {len(reqs)} requests on {cfg.name} "
          f"(attn={cfg.attn_kind})...")
    t0 = time.perf_counter()
    if args.lockstep:
        engine = ServingEngine(cfg, params, mesh, max_len=256)
        outs = engine.generate(reqs, temperature=0.8)
    else:
        # DESIGN §8 walkthrough, step 2 — the engine: slot_shards > 1
        # shards the pool cache, every per-slot control vector, and the
        # (K, S) macro-step token buffer over `data` in static contiguous
        # slot blocks. Admission balances across shards; eviction is a
        # shard-local slot overwrite; the K-tick decode scan runs with
        # zero cross-shard collectives (engine.decode_hlo() shows the
        # compiled proof).
        pc = (PrefixCache(args.prefix_cache * 1024 * 1024)
              if args.prefix_cache else None)
        engine = ContinuousServingEngine(
            cfg, params, mesh, prefix_cache=pc,
            serving=ServingConfig(num_slots=args.slots, max_len=256,
                                  prefill_chunk=8, temperature=0.8,
                                  slot_shards=args.slot_shards,
                                  max_queue=args.max_queue,
                                  overload_policy=args.overload_policy,
                                  page_size=args.page_size,
                                  speculative=args.speculative,
                                  spec_gamma=args.spec_gamma))
        # Typed admission (DESIGN.md §10): a refused request raises an
        # AdmissionError subclass carrying queue_depth/max_queue, so a
        # caller can back off or report precisely — no message parsing.
        admitted = []                      # (rid, request) pairs
        for r in reqs:
            try:
                admitted.append((engine.submit(r), r))
            except AdmissionError as e:
                print(f"  refused ({type(e).__name__}, queue "
                      f"{e.queue_depth}/{e.max_queue}): {e}")
        out_map, summary = engine.run()
        outs = [out_map[rid] for rid, _ in admitted]
        reqs = [r for _, r in admitted]
        print(f"  finish reasons: {summary['finish_reasons']}")
        # DESIGN §8 walkthrough, step 3 — the contract: rerun this script
        # with/without --slot-shards and diff the token lines below; they
        # are byte-identical (slot_shards in the summary confirms the
        # pool really sharded rather than hitting the divisibility
        # fallback).
        print(f"  pool: {args.slots} slots x {summary['slot_shards']} "
              f"shard(s) | occupancy "
              f"{summary['mean_slot_occupancy']:.2f} | TTFT p50 "
              f"{summary['ttft_ticks_p50']} ticks | "
              f"{summary['decode_tokens_per_s']:.1f} decode tok/s")
        # DESIGN §11: page-pool pressure and prefix-cache reuse, when on.
        if summary["num_pages"]:
            print(f"  pages: {summary['pages_peak']}/"
                  f"{summary['num_pages']} peak in use "
                  f"({args.page_size} rows/page); leaked "
                  f"{summary['final_pages_in_use']}")
        if pc is not None:
            print(f"  prefix cache: {summary['prefix_hits']} hits, "
                  f"{summary['prefix_tokens_reused']} prompt tokens "
                  f"reused | {pc.stats()}")
        # DESIGN §13: draft-verify amortization — one verifier dispatch
        # emits up to K * (gamma + 1) tokens.
        if summary["speculative"]:
            print(f"  speculative: gamma={summary['spec_gamma']} | "
                  f"acceptance {summary['draft_acceptance_rate']:.3f} "
                  f"({summary['draft_tokens_accepted']}/"
                  f"{summary['draft_tokens_proposed']} drafts) | "
                  f"{summary['tokens_per_dispatch']:.1f} tok/dispatch")
    dt = time.perf_counter() - t0
    total = sum(len(o) for o in outs)
    for i, o in enumerate(outs):
        print(f"req {i}: prompt_len={len(reqs[i].prompt)} -> {o[:12]}...")
    print(f"\n{total} tokens in {dt:.2f}s ({total / dt:.1f} tok/s batched)")

    # The long-context pitch: decode state size is context-independent.
    c_small = api.abstract_cache(cfg, args.batch, 256)
    c_huge = api.abstract_cache(cfg, args.batch, 524_288)

    def nbytes(tree):
        return sum(np.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))
    print(f"decode-state bytes @256 ctx:  {nbytes(c_small):,}")
    print(f"decode-state bytes @524288 ctx: {nbytes(c_huge):,} "
          f"(constant — the paper's O(1) long-context memory)")


if __name__ == "__main__":
    main()
