"""Content-addressed prefix cache (DESIGN.md §11): keying/collision
safety, chunk-multiple candidate discipline, LRU eviction under the byte
budget with refcount pinning, full-hit logits requirements — and
engine-level cached-vs-cold stream *byte*-identity for both cache regimes
(constant-state and KV ring, paged and unpaged)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ServingConfig
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.serving.engine import ContinuousServingEngine, Request
from repro.serving.prefix_cache import PrefixCache, token_digest


# ---------------------------------------------------------------------------
# Unit level
# ---------------------------------------------------------------------------


def _cache(n=100):
    return {"state": jnp.zeros((n,), jnp.float32)}    # 4n bytes


def test_digest_collision_cannot_false_hit():
    """Even a pathological digest function (everything collides) never
    returns a wrong entry: the stored tokens are compared outright."""
    pc = PrefixCache(1 << 20, digest_fn=lambda toks: b"collide")
    a, b = np.int32([1, 2, 3, 4]), np.int32([1, 2, 9, 9])
    pc.insert(a, _cache(), logits=jnp.zeros((1, 1, 8)))
    got = pc.lookup(b, chunk=4)
    assert got is None and pc.misses == 1
    got = pc.lookup(a, chunk=4)
    assert got is not None and got.length == 4 and pc.hits == 1


def test_token_digest_is_length_and_content_addressed():
    assert token_digest(np.int32([1, 2])) != token_digest(np.int32([2, 1]))
    assert token_digest(np.int32([1, 2])) == token_digest(
        np.asarray([1, 2], np.int64))             # canonical int32 bytes


def test_lookup_serves_only_chunk_multiples():
    """Proper prefixes at non-chunk-multiple lengths are never served —
    the suffix chunk schedule must match a cold prefill's."""
    pc = PrefixCache(1 << 20)
    toks = np.int32(range(10))
    pc.insert(toks[:5], _cache())                 # not a multiple of 4
    pc.insert(toks[:4], _cache())
    got = pc.lookup(toks, chunk=4)
    assert got is not None and got.length == 4    # 8 absent, 5 skipped
    pc.insert(toks[:8], _cache())
    got = pc.lookup(toks, chunk=4)
    assert got.length == 8                        # longest multiple wins


def test_full_hit_requires_stored_logits():
    """A full-length entry without logits cannot seed token 0, so lookup
    falls through to a proper-prefix candidate; insert() upgrades the
    entry in place once logits become available."""
    pc = PrefixCache(1 << 20)
    toks = np.int32(range(8))
    pc.insert(toks, _cache())                     # full length, no logits
    pc.insert(toks[:4], _cache())
    got = pc.lookup(toks, chunk=4)
    assert got.length == 4                        # full entry skipped
    e = pc.insert(toks, _cache(), logits=jnp.ones((1, 1, 8)))
    assert e.logits is not None                   # upgraded, not duplicated
    got = pc.lookup(toks, chunk=4)
    assert got.length == 8 and got is e
    assert len(pc) == 2


def test_lru_eviction_under_byte_budget():
    pc = PrefixCache(900)
    lg = jnp.zeros((1, 1, 4), jnp.float32)        # 16 bytes
    t = np.int32(range(12))
    e1 = pc.insert(t[:4], _cache(100), logits=lg)     # 416 bytes
    pc.insert(t[:8], _cache(100), logits=lg)          # 416 bytes
    assert pc.lookup(t[:4], chunk=4) is e1        # refresh e1's stamp
    pc.insert(t[:12], _cache(100), logits=lg)     # needs room -> evict LRU
    assert pc.evictions == 1 and len(pc) == 2
    assert pc.lookup(t[:4], chunk=4) is e1        # refreshed entry survives
    got = pc.lookup(t[:8], chunk=4)
    assert got is e1                              # LRU victim gone: falls
    assert got.length == 4                        # back to the short prefix
    assert pc.nbytes <= 900


def test_referenced_entries_are_never_evicted():
    pc = PrefixCache(1000)
    t = np.int32(range(8))
    e1 = pc.insert(t[:4], _cache(100))
    e2 = pc.insert(t[:8], _cache(100),
                   logits=jnp.zeros((1, 1, 4), jnp.float32))
    pc.acquire(e1)
    pc.acquire(e2)
    assert pc.insert(t[:6], _cache(100)) is None  # both pinned: no room
    assert len(pc) == 2 and pc.evictions == 0
    pc.release(e1)
    assert pc.insert(t[:6], _cache(100)) is not None
    assert pc.lookup(t[:8], chunk=4) is e2        # pinned entry survived


def test_insert_copy_snapshots_buffers():
    """copy=True must deep-copy: mutating (donating) the caller's buffer
    after insert cannot corrupt the stored snapshot."""
    pc = PrefixCache(1 << 20)
    src = {"state": jnp.ones((4,), jnp.float32)}
    e = pc.insert(np.int32([1, 2, 3, 4]), src)
    src["state"] = src["state"] * 0               # caller moves on
    np.testing.assert_array_equal(np.asarray(e.cache["state"]),
                                  np.ones(4, np.float32))


def test_stats_shape():
    pc = PrefixCache(1 << 20)
    pc.insert(np.int32([1, 2]), _cache(), logits=jnp.zeros((1, 1, 4)))
    pc.lookup(np.int32([1, 2]), chunk=2)
    pc.lookup(np.int32([7, 7]), chunk=2)
    s = pc.stats()
    assert s["hits"] == 1 and s["misses"] == 1 and s["hit_rate"] == 0.5
    assert s["entries"] == 1 and s["tokens_reused"] == 2
    assert s["bytes"] == pc.nbytes > 0


# ---------------------------------------------------------------------------
# Engine level: cached-vs-cold byte identity
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


def _shared_prefix_reqs(cfg, n=4, prefix_len=8, seed=17):
    """n prompts sharing a prefix_len system prefix + 1 unique token."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(3, cfg.vocab_size, size=prefix_len)
    return [Request(np.concatenate([sys_prompt, [3 + i]]).astype(np.int32),
                    max_new_tokens=8, arrival_time=float(i))
            for i in range(n)]


def _poisson_prefix_reqs(cfg, n=4, chunk=4, seed=99):
    """n Poisson arrivals (one per tick on average), each one shared
    2-chunk system prefix plus a unique suffix of 1..chunk tokens."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(3, cfg.vocab_size, size=2 * chunk)
    t, reqs = 0.0, []
    for _ in range(n):
        t += rng.exponential(1.0)
        suffix = rng.integers(3, cfg.vocab_size,
                              size=int(rng.integers(1, chunk + 1)))
        reqs.append(Request(
            np.concatenate([sys_prompt, suffix]).astype(np.int32),
            max_new_tokens=16, arrival_time=t))
    return reqs


def _serve(cfg, params, mesh, reqs, *, pc=None, page_size=0, macro_ticks=4):
    eng = ContinuousServingEngine(
        cfg, params, mesh, prefix_cache=pc,
        serving=ServingConfig(num_slots=2, max_len=32, prefill_chunk=4,
                              macro_ticks=macro_ticks, page_size=page_size))
    outs, summary = eng.run(
        [Request(r.prompt, max_new_tokens=r.max_new_tokens,
                 arrival_time=r.arrival_time) for r in reqs])
    return eng, outs, summary


@pytest.mark.serving
@pytest.mark.parametrize("arch_kind,page_size,make_reqs,macro_ticks", [
    # constant-state (no paging)
    (("slayformer-124m", "slay"), 0, _shared_prefix_reqs, 4),
    # KV ring, unpaged
    (("slayformer-124m", "softmax"), 0, _shared_prefix_reqs, 4),
    # KV ring, paged
    (("slayformer-124m", "softmax"), 8, _shared_prefix_reqs, 4),
    # KV ring, paged, Poisson arrivals at K=8
    (("slayformer-124m", "softmax"), 8, _poisson_prefix_reqs, 8),
], ids=["constant_state", "kv_ring", "kv_ring_paged", "kv_ring_paged_poisson"])
def test_cached_streams_byte_identical_to_cold(arch_kind, page_size,
                                               make_reqs, macro_ticks, mesh):
    """A warmed shared cache full-hits every request of a replayed trace,
    the streams are byte-identical to the cold run and the median TTFT
    drops below the cold run's, in every cache regime; with paging on, no
    pages leak."""
    arch, kind = arch_kind
    cfg = configs.get_smoke_config(arch, attn_kind=kind)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    reqs = make_reqs(cfg)
    kw = {"page_size": page_size, "macro_ticks": macro_ticks}
    _, cold, s_cold = _serve(cfg, params, mesh, reqs, **kw)  # truly cold
    assert s_cold["prefix_hits"] == 0
    pc = PrefixCache(64 * 1024 * 1024)
    _serve(cfg, params, mesh, reqs, pc=pc, **kw)             # warm-up
    _, warm, s_warm = _serve(cfg, params, mesh, reqs, pc=pc, **kw)
    assert s_warm["prefix_hits"] == len(reqs)     # replay: all full hits
    assert s_warm["prefix_tokens_reused"] == sum(len(r.prompt)
                                                 for r in reqs)
    assert s_warm["ttft_ticks_p50"] < s_cold["ttft_ticks_p50"]
    for rid in cold:
        np.testing.assert_array_equal(cold[rid], warm[rid])
    if page_size:
        assert s_cold["final_pages_in_use"] == 0
        assert s_warm["final_pages_in_use"] == 0


@pytest.mark.serving
def test_partial_prefix_hit_within_one_engine(mesh):
    """Within a single engine, later arrivals partial-hit the shared
    chunk-boundary snapshot stored by the first request; their streams
    match a no-cache run byte-for-byte and only suffix tokens prefill."""
    cfg = configs.get_smoke_config("slayformer-124m", attn_kind="softmax")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    # Arrivals spaced out so request 0 finishes prefill (and inserts its
    # chunk-boundary snapshots) before the others are admitted.
    reqs = _shared_prefix_reqs(cfg)
    reqs = [Request(r.prompt, max_new_tokens=8, arrival_time=i * 30.0)
            for i, r in enumerate(reqs)]
    _, plain, _ = _serve(cfg, params, mesh, reqs)
    e, outs, s = _serve(cfg, params, mesh, reqs, pc=PrefixCache(1 << 26))
    assert s["prefix_hits"] >= len(reqs) - 1      # all but the first
    assert s["prefix_tokens_reused"] >= (len(reqs) - 1) * 8
    for rid in plain:
        np.testing.assert_array_equal(plain[rid], outs[rid])
    # The hit requests absorbed only their suffix at prefill time.
    assert s["prompt_tokens"] < sum(len(r.prompt) for r in reqs)
    for rid, st in e.metrics.per_request.items():
        if st.prefix_cached:
            assert st.prefix_tokens == 8          # the 2-chunk system prefix


@pytest.mark.serving
def test_identical_prompt_full_hit_skips_prefill(mesh):
    """The second submission of an identical prompt seeds from the stored
    snapshot + logits: zero prompt tokens absorbed, identical stream."""
    cfg = configs.get_smoke_config("slayformer-124m", attn_kind="slay")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.int32([4, 5, 6, 7, 8, 9, 10, 11])
    reqs = [Request(prompt, max_new_tokens=8, arrival_time=0.0),
            Request(prompt, max_new_tokens=8, arrival_time=40.0)]
    e, outs, s = _serve(cfg, params, mesh, reqs, pc=PrefixCache(1 << 26))
    assert s["prefix_hits"] == 1
    np.testing.assert_array_equal(outs[0], outs[1])
    st = e.metrics.per_request[1]
    assert st.prefix_cached and st.prefix_tokens == len(prompt)
    assert s["prompt_tokens"] == len(prompt)      # absorbed exactly once
    # TTFT split metrics surface the win.
    assert s["ttft_cached_ticks_p50"] is not None
    assert s["ttft_cold_ticks_p50"] is not None
