"""Multi-device driver for the sharded serving slot-pool tests.

Run in a subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(jax device count is fixed at first init, so the forced-device flag cannot
be set from inside the already-initialized tier-1 process —
``tests/test_serving_sharded.py`` spawns this file per check). CI also
invokes it directly under the same flag.

Each check exercises the DESIGN.md §8 contract on real multi-device
shardings: byte-identical token streams between mesh=(1,) and
mesh=(data=4,), shard-local eviction/reuse, the num_slots divisibility
fallback, and the zero-collective decode hot loop.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python tests/sharded_driver.py --check parity
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import configs
from repro.analysis import hlo as hlo_lib
from repro.configs.base import ServingConfig
from repro.launch.mesh import make_serving_mesh
from repro.models import api
from repro.serving.engine import ContinuousServingEngine, Request


def poisson_trace(rng, n: int, rate: float, prompt_range, vocab: int,
                  max_new: int) -> list[Request]:
    """n requests with exp(rate) inter-arrival ticks and random prompts."""
    t = 0.0
    reqs = []
    lo, hi = prompt_range
    for _ in range(n):
        t += rng.exponential(1.0 / rate)
        plen = int(rng.integers(lo, hi + 1))
        prompt = rng.integers(3, vocab, size=plen).astype(np.int32)
        reqs.append(Request(prompt, max_new_tokens=max_new,
                            arrival_time=t))
    return reqs


def _assert_collective_free(hlo_text: str, label: str) -> int:
    """§8 contract via the op-level analyzer (not a substring grep —
    parsed opcodes catch async forms like ``all-gather-start`` and don't
    trip on fusion *names* that merely mention a collective). Also holds
    the no-host-callback line (§14 HLO002). Returns the op count."""
    module = hlo_lib.parse_hlo(hlo_text)
    findings = (hlo_lib.check_no_collectives(module, label)
                + hlo_lib.check_no_host_ops(module, label))
    assert not findings, "\n".join(f.render() for f in findings)
    return len(module.instructions)


def _setup(attn_kind="slay"):
    cfg = configs.get_smoke_config("slayformer-124m", attn_kind=attn_kind)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _poisson_trace(cfg, n=6, rate=0.5, prompt_range=(3, 12), max_new=6,
                   seed=1234):
    """Mixed-length Poisson arrivals for the checks below."""
    return poisson_trace(np.random.default_rng(seed), n, rate, prompt_range,
                         cfg.vocab_size, max_new)


def _run(cfg, params, *, data, num_slots, macro_ticks, temperature=0.0,
         reqs=None, slot_shards=0, page_size=0):
    mesh = make_serving_mesh(data)
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=num_slots, max_len=64,
                              prefill_chunk=4, macro_ticks=macro_ticks,
                              temperature=temperature, seed=3,
                              slot_shards=slot_shards,
                              page_size=page_size))
    outs, summary = eng.run(list(reqs))
    return eng, outs, summary


def check_parity():
    """Byte-identical streams mesh=(1,) vs mesh=(data=4,) at K=8 and K=1,
    greedy and sampled, both cache regimes; jit budget holds sharded."""
    assert jax.device_count() >= 4, jax.device_count()
    for kind, temps, ks in (("slay", (0.0, 0.8), (8, 1)),
                            ("softmax", (0.0,), (8,))):
        cfg, params = _setup(kind)
        reqs = _poisson_trace(cfg)
        for temperature in temps:
            for k in ks:
                _, o1, s1 = _run(cfg, params, data=1, num_slots=4,
                                 macro_ticks=k, temperature=temperature,
                                 reqs=reqs)
                e4, o4, s4 = _run(cfg, params, data=4, num_slots=4,
                                  macro_ticks=k, temperature=temperature,
                                  reqs=reqs)
                assert s1["slot_shards"] == 1 and s4["slot_shards"] == 4
                assert s4["requests_completed"] == len(reqs)
                for rid in o1:
                    np.testing.assert_array_equal(o1[rid], o4[rid])
                # Scheduling trajectory is mesh-shape-independent too.
                assert s1["ticks"] == s4["ticks"]
                assert s1["decode_dispatches"] == s4["decode_dispatches"]
                # PR-3 recompile budget survives sharding.
                assert e4.jit_cache_entries().get("macro_decode", 1) == 1
                print(f"parity OK kind={kind} T={temperature} K={k}")


def check_evict_reuse():
    """Shard-local eviction/reuse: 2 slots per shard, burst arrivals so the
    pool fills — admissions spread across shards before doubling up on
    any, every reuse honours the finished-before-admitted invariant, and
    streams match the single-shard run."""
    cfg, params = _setup()
    # Burst: everything arrives at once, short prompts (one prefill chunk
    # per admission), K=1 so admissions aren't quantized to macro-step
    # boundaries — the pool actually fills before anything finishes.
    reqs = _poisson_trace(cfg, n=10, rate=100.0, prompt_range=(3, 4),
                          max_new=16, seed=7)
    _, o1, _ = _run(cfg, params, data=1, num_slots=8, macro_ticks=1,
                    reqs=reqs)
    e4, o4, s4 = _run(cfg, params, data=4, num_slots=8, macro_ticks=1,
                      reqs=reqs)
    assert s4["requests_completed"] == 10
    for rid in o1:
        np.testing.assert_array_equal(o1[rid], o4[rid])
    stats = sorted(e4.metrics.per_request.values(),
                   key=lambda st: (st.admitted, st.rid))
    # Burst fill: the first four admissions land on four distinct shards
    # (load balancing), not on shard 0's two slots back-to-back.
    first4 = [e4.sched.shard_of(st.slot) for st in stats[:4]]
    assert sorted(first4) == [0, 1, 2, 3], first4
    by_slot = {}
    for st in stats:
        by_slot.setdefault(st.slot, []).append(st)
    for tenants in by_slot.values():
        for prev, nxt in zip(tenants, tenants[1:]):
            assert nxt.admitted >= prev.finished   # shard-local slot reuse
    assert any(len(v) >= 2 for v in by_slot.values())   # reuse happened
    print("evict/reuse OK: slots", {s: len(v) for s, v in by_slot.items()})


def check_fallback():
    """num_slots=6 over data=4 does not divide: the pool replicates, the
    drop is recorded like the rule-engine fallback, streams stay exact."""
    cfg, params = _setup()
    reqs = _poisson_trace(cfg, n=5, seed=11)
    _, o1, _ = _run(cfg, params, data=1, num_slots=6, macro_ticks=8,
                    reqs=reqs)
    e6, o6, s6 = _run(cfg, params, data=4, num_slots=6, macro_ticks=8,
                      reqs=reqs)
    assert s6["slot_shards"] == 1
    assert e6.slot_shard_fallbacks == [("slots", 6, "data")]
    for rid in o1:
        np.testing.assert_array_equal(o1[rid], o6[rid])
    # Demanding an impossible shard count is a hard error, not a fallback.
    try:
        _run(cfg, params, data=4, num_slots=6, macro_ticks=8, reqs=[],
             slot_shards=2)
    except ValueError as e:
        assert "slot_shards" in str(e)
    else:
        raise AssertionError("slot_shards=2 on a data=4 mesh must raise")
    print("fallback OK:", e6.slot_shard_fallbacks)


def check_collectives():
    """The compiled K-tick decode macro-step has zero cross-shard
    collectives on mesh=(data=4,), for both cache regimes — and the
    sharding specs actually place the slot dim on `data`."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd
    from repro.models import api as mapi

    mesh = make_serving_mesh(4)
    v = shd.serving_vector_sharding(mesh, num_slots=4)
    assert v.spec == P("data"), v.spec
    buf = shd.serving_vector_sharding(mesh, num_slots=4, leading=1)
    assert buf.spec == P(None, "data"), buf.spec

    for kind in ("slay", "softmax"):
        cfg, params = _setup(kind)
        c_abs = mapi.abstract_cache(cfg, 4, 64)
        c_sh = shd.serving_cache_sharding(mesh, shd.DEFAULT_RULES, c_abs,
                                          num_slots=4)
        for leaf, sh in zip(jax.tree.leaves(c_abs), jax.tree.leaves(c_sh)):
            dim = 1 if len(leaf.shape) >= 2 else 0
            assert len(sh.spec) > dim and sh.spec[dim] == "data", \
                (leaf.shape, sh.spec)
        eng = ContinuousServingEngine(
            cfg, params, mesh,
            serving=ServingConfig(num_slots=4, max_len=64, prefill_chunk=4,
                                  macro_ticks=8))
        assert eng.slot_shards == 4
        nops = _assert_collective_free(eng.decode_hlo(),
                                       f"decode_hlo[{kind}]")
        print(f"collectives OK kind={kind} (none in {nops} ops)")


def check_paged():
    """Paged slot memory on a sharded pool (DESIGN.md §11): streams are
    byte-identical to the unpaged single-shard run, the shard-aligned
    page allocator never crosses a shard block, no pages leak, and the
    compiled decode macro-step stays collective-free with the page
    gather/scatter inside it."""
    cfg, params = _setup("softmax")        # KV ring: the paged regime
    assert api.supports_paging(cfg)
    reqs = _poisson_trace(cfg, n=8, seed=23, max_new=8)
    _, o1, _ = _run(cfg, params, data=1, num_slots=4, macro_ticks=8,
                    reqs=reqs)
    e4, o4, s4 = _run(cfg, params, data=4, num_slots=4, macro_ticks=8,
                      reqs=reqs, page_size=16)
    assert s4["requests_completed"] == len(reqs)
    for rid in o1:
        np.testing.assert_array_equal(o1[rid], o4[rid])
    assert s4["num_pages"] == 16 and s4["pages_peak"] >= 1, s4
    assert s4["final_pages_in_use"] == 0, s4
    e4.page_pool.check()                    # allocator invariant audit
    nops = _assert_collective_free(e4.decode_hlo(), "decode_hlo[paged]")
    print(f"paged OK: sharded paged streams byte-identical, "
          f"pages_peak={s4['pages_peak']}, no collectives "
          f"({nops} ops)")


def check_kernel():
    """The Pallas decode kernel on a slot-sharded pool: it runs once per
    shard under shard_map (GSPMD cannot partition a pallas_call), streams
    stay byte-identical to the one-device pool, and the decode step keeps
    zero collectives. On a TPU the kernel path is picked by platform; here
    the dispatch is forced to the kernel, run in interpret mode."""
    from repro.kernels import decode_step as dk
    from repro.kernels import ops

    rows = []
    real, real_use = dk.decode_linear_attention, ops._use_kernel

    def interpret_kernel(*args, **kwargs):
        rows.append(args[0].shape[0])
        return real(*args, **{**kwargs, "interpret": True})

    ops._use_kernel = lambda interpret: True
    dk.decode_linear_attention = interpret_kernel
    try:
        cfg, params = _setup("slay")
        reqs = _poisson_trace(cfg)
        _, o1, _ = _run(cfg, params, data=1, num_slots=4, macro_ticks=8,
                        reqs=reqs)
        e4, o4, s4 = _run(cfg, params, data=4, num_slots=4, macro_ticks=8,
                          reqs=reqs)
        assert s4["slot_shards"] == 4
        for rid in o1:
            np.testing.assert_array_equal(o1[rid], o4[rid])
        nops = _assert_collective_free(e4.decode_hlo(), "decode_hlo[kernel]")
    finally:
        dk.decode_linear_attention, ops._use_kernel = real, real_use
    # One kv row per (slot, head): 4 slots whole, 1 slot per shard.
    heads = cfg.num_kv_heads
    assert set(rows) == {4 * heads, heads}, rows
    print(f"kernel OK: per-shard decode kernel, streams byte-identical, "
          f"no collectives ({nops} ops)")


def check_ring():
    """The KV-ring decode writes one row per live slot in place, per shard
    on a 2-shard slot pool: rings, every other cache leaf and logits equal
    the one-device one-hot reference bit for bit, as slot positions wrap
    past max_len under random active masks, and the sharded decode step
    has no collectives."""
    from test_decode_hot_loop import RING_CFGS, ring_parity

    mesh = make_serving_mesh(2)
    for kind, make in sorted(RING_CFGS.items()):
        text = ring_parity(make(), mesh)
        nops = _assert_collective_free(text, f"ring[{kind}]")
        print(f"ring OK kind={kind}: 2 shards match the one-hot reference, "
              f"no collectives ({nops} ops)")


CHECKS = {"parity": check_parity, "evict_reuse": check_evict_reuse,
          "fallback": check_fallback, "collectives": check_collectives,
          "paged": check_paged, "kernel": check_kernel, "ring": check_ring}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", choices=sorted(CHECKS) + ["all"],
                    default="all")
    args = ap.parse_args()
    names = sorted(CHECKS) if args.check == "all" else [args.check]
    for name in names:
        CHECKS[name]()
    print(f"sharded_driver OK: {', '.join(names)}")


if __name__ == "__main__":
    main()
