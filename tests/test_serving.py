"""Serving engines: lockstep reference (greedy determinism, eos actual
lengths, constant-state vs KV decode) and the continuous-batching engine
(staggered admission, eos eviction + slot reuse, streamed parity, chunked
prefill continuation, both cache regimes)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ServingConfig
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.serving.engine import (ContinuousServingEngine, Request,
                                  ServingEngine)


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_smoke_config("slayformer-124m")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    return cfg, params, mesh


@pytest.fixture(scope="module")
def setup_softmax():
    cfg = configs.get_smoke_config("slayformer-124m", attn_kind="softmax")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    return cfg, params, mesh


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg.vocab_size, size=n).astype(np.int32)
            for n in lengths]


def test_generate_batched(setup):
    cfg, params, mesh = setup
    eng = ServingEngine(cfg, params, mesh, max_len=64)
    reqs = [Request(np.array([1, 2, 3], np.int32), max_new_tokens=4),
            Request(np.array([4, 5], np.int32), max_new_tokens=6)]
    outs = eng.generate(reqs)
    assert len(outs) == 2
    assert outs[0].shape == (4,) and outs[1].shape == (6,)
    for o in outs:
        assert np.all((o >= 0) & (o < cfg.vocab_size))


def test_greedy_is_deterministic(setup):
    cfg, params, mesh = setup
    eng = ServingEngine(cfg, params, mesh, max_len=64)
    reqs = [Request(np.arange(1, 6, dtype=np.int32), max_new_tokens=5)]
    a = eng.generate(reqs)[0]
    b = eng.generate(reqs)[0]
    np.testing.assert_array_equal(a, b)


def test_eos_returns_actual_length(setup):
    """EOS fix: the returned array ends at the eos token (inclusive) — no
    zero padding out to max_new_tokens."""
    cfg, params, mesh = setup
    eng = ServingEngine(cfg, params, mesh, max_len=64)
    reqs = [Request(np.array([1, 2], np.int32), max_new_tokens=8)]
    full = eng.generate(reqs)[0]
    assert full.shape == (8,)
    stop = int(full[2])              # this value becomes the EOS id
    cut = int(np.argmax(full == stop))   # its first occurrence
    reqs_eos = [Request(np.array([1, 2], np.int32), max_new_tokens=8,
                        eos_id=stop)]
    out = eng.generate(reqs_eos)[0]
    assert out.shape == (cut + 1,)   # through EOS inclusive, then stops
    np.testing.assert_array_equal(out, full[:cut + 1])


def test_decode_matches_forward(setup):
    """Teacher-forced decode logits must match the full forward pass —
    the constant-state SLAY path is an exact reformulation, not an
    approximation of the prefill math."""
    cfg, params, _ = setup
    key = jax.random.PRNGKey(1)
    toks = jax.random.randint(key, (1, 12), 0, cfg.vocab_size)
    logits_full, _ = api.forward(params, cfg, {"tokens": toks})
    _, cache = api.prefill(params, cfg, {"tokens": toks[:, :6]})
    errs = []
    for t in range(6, 12):
        logits_t, cache = api.decode_step(params, cfg, cache, toks[:, t:t+1])
        errs.append(np.max(np.abs(
            np.asarray(logits_t[:, 0], np.float32)
            - np.asarray(logits_full[:, t], np.float32))))
    assert max(errs) < 0.15   # bf16 activations, fp32 state


def test_prefill_logits_match_forward(setup):
    cfg, params, _ = setup
    key = jax.random.PRNGKey(2)
    toks = jax.random.randint(key, (2, 10), 0, cfg.vocab_size)
    logits_full, _ = api.forward(params, cfg, {"tokens": toks})
    logits_pre, _ = api.prefill(params, cfg, {"tokens": toks})
    np.testing.assert_allclose(
        np.asarray(logits_pre[:, 0], np.float32),
        np.asarray(logits_full[:, -1], np.float32), atol=0.1)


def test_softmax_kv_cache_decode(setup_softmax):
    """The KV-ring-buffer path (softmax backend) also decodes consistently."""
    cfg, params, _ = setup_softmax
    key = jax.random.PRNGKey(1)
    toks = jax.random.randint(key, (1, 12), 0, cfg.vocab_size)
    logits_full, _ = api.forward(params, cfg, {"tokens": toks})
    _, cache = api.prefill(params, cfg, {"tokens": toks[:, :6]})
    errs = []
    for t in range(6, 12):
        logits_t, cache = api.decode_step(params, cfg, cache, toks[:, t:t+1])
        errs.append(np.max(np.abs(
            np.asarray(logits_t[:, 0], np.float32)
            - np.asarray(logits_full[:, t], np.float32))))
    assert max(errs) < 0.15


def test_linear_state_is_constant_size(setup):
    """The paper's long-context win: SLAY decode cache size is independent
    of max_len."""
    cfg, _, _ = setup
    c1 = api.abstract_cache(cfg, 2, 128)
    c2 = api.abstract_cache(cfg, 2, 4096)
    s1 = sum(np.prod(x.shape) for x in jax.tree.leaves(c1.attn))
    s2 = sum(np.prod(x.shape) for x in jax.tree.leaves(c2.attn))
    assert s1 == s2

    cfg_sm = configs.get_smoke_config("slayformer-124m",
                                      attn_kind="softmax")
    k1 = api.abstract_cache(cfg_sm, 2, 128)
    k2 = api.abstract_cache(cfg_sm, 2, 4096)
    b1 = sum(np.prod(x.shape) for x in jax.tree.leaves(k1.attn))
    b2 = sum(np.prod(x.shape) for x in jax.tree.leaves(k2.attn))
    assert b2 > 8 * b1                # KV cache grows with context


# ---------------------------------------------------------------------------
# Chunked prefill + slot-pooled cache surface
# ---------------------------------------------------------------------------


@pytest.mark.serving
@pytest.mark.parametrize("kind", ["slay", "softmax"])
def test_chunked_prefill_matches_whole_prompt(kind):
    """Feeding a prompt chunk-by-chunk ends in the same logits/state as a
    whole-prompt prefill, for both cache regimes."""
    cfg = configs.get_smoke_config("slayformer-124m", attn_kind=kind)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 11), 0,
                              cfg.vocab_size)
    lg_full, cache_full = api.prefill(params, cfg, {"tokens": toks},
                                      max_len=64)
    cache = api.init_cache(cfg, 1, 64)
    for lo, hi in ((0, 4), (4, 8), (8, 11)):
        lg, cache = api.prefill_chunk(cfg, params, cache, toks[:, lo:hi])
    np.testing.assert_allclose(np.asarray(lg, np.float32),
                               np.asarray(lg_full, np.float32), atol=0.1)
    assert np.asarray(cache.pos).tolist() == [11]
    # Decode continuation from both caches agrees token-for-token.
    tok = jnp.argmax(lg_full[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(3):
        l1, cache_full = api.decode_step(params, cfg, cache_full, tok)
        l2, cache = api.decode_step(params, cfg, cache, tok)
        np.testing.assert_allclose(np.asarray(l1, np.float32),
                                   np.asarray(l2, np.float32), atol=0.1)
        tok = jnp.argmax(l1[:, -1], -1).astype(jnp.int32)[:, None]


@pytest.mark.serving
def test_chunked_prefill_local_global_mix():
    """gemma2-style local/global layer alternation chunks exactly too."""
    cfg = configs.get_smoke_config("gemma2-27b")
    assert api.supports_chunked_prefill(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 10), 0,
                              cfg.vocab_size)
    lg_full, _ = api.prefill(params, cfg, {"tokens": toks}, max_len=64)
    cache = api.init_cache(cfg, 1, 64)
    for lo, hi in ((0, 6), (6, 10)):
        lg, cache = api.prefill_chunk(cfg, params, cache, toks[:, lo:hi])
    np.testing.assert_allclose(np.asarray(lg, np.float32),
                               np.asarray(lg_full, np.float32), atol=0.2)


@pytest.mark.serving
@pytest.mark.parametrize("kind", ["slay", "softmax"])
def test_slot_write_and_reset(kind):
    """Admission/eviction are single-slot overwrites: neighbours' bytes are
    bit-identical before and after."""
    cfg = configs.get_smoke_config("slayformer-124m", attn_kind=kind)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 7), 0,
                              cfg.vocab_size)
    pool = api.init_cache(cfg, 3, 32)
    _, req = api.prefill(params, cfg, {"tokens": toks}, max_len=32)
    # Put something nonzero in slot 2 first, then admit into slot 1.
    pool = api.write_slot(cfg, pool, req, 2)
    before = [np.asarray(x).copy() for x in jax.tree.leaves(pool)]
    pool = api.write_slot(cfg, pool, req, 1)
    assert np.asarray(pool.pos).tolist() == [0, 7, 7]
    for x_b, x_a in zip(before, jax.tree.leaves(pool)):
        a = np.asarray(x_a)
        if a.ndim >= 2 and a.shape[1] == 3:       # (nl, B, ...) leaves
            np.testing.assert_array_equal(x_b[:, 2], a[:, 2])
            np.testing.assert_array_equal(x_b[:, 0], a[:, 0])
    pool = api.reset_slot(cfg, pool, 1)
    assert np.asarray(pool.pos).tolist() == [0, 0, 7]
    zeroed = jax.tree.map(lambda x: np.all(np.asarray(x[:, 1]) == 0)
                          if np.asarray(x).ndim >= 2
                          and np.asarray(x).shape[1] == 3 else True, pool.attn)
    assert all(jax.tree.leaves(zeroed))


# ---------------------------------------------------------------------------
# Continuous batching engine
# ---------------------------------------------------------------------------


@pytest.mark.serving
def test_continuous_staggered_parity_and_slot_reuse(setup):
    """Requests with staggered arrivals and mixed lengths on a 2-slot pool:
    (a) token-level parity with the lockstep reference, (b) a finished slot
    is reused by a queued request."""
    cfg, params, mesh = setup
    prompts = _prompts(cfg, (5, 9, 3, 7, 4))
    reqs = [Request(p, max_new_tokens=6, arrival_time=float(2 * i))
            for i, p in enumerate(prompts)]
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=2, max_len=64, prefill_chunk=4))
    outs, summary = eng.run(reqs)
    assert summary["requests_completed"] == len(reqs)

    ref = ServingEngine(cfg, params, mesh, max_len=64)
    for i, p in enumerate(prompts):
        want = ref.generate([Request(p, max_new_tokens=6)])[0]
        np.testing.assert_array_equal(outs[i], want)

    # 5 requests over 2 slots: some slot must have served >= 2 requests,
    # and the later tenant was admitted only after the earlier finished.
    stats = eng.metrics.per_request
    by_slot = {}
    for st in stats.values():
        by_slot.setdefault(st.slot, []).append(st)
    assert max(len(v) for v in by_slot.values()) >= 2
    for tenants in by_slot.values():
        tenants.sort(key=lambda s: s.admitted)
        for prev, nxt in zip(tenants, tenants[1:]):
            assert nxt.admitted >= prev.finished


@pytest.mark.serving
def test_continuous_eos_eviction_immediate_reuse(setup):
    """An EOS hit evicts the slot and the next queued request takes it —
    on a 1-slot pool the second request can only complete via that reuse."""
    cfg, params, mesh = setup
    p0, p1 = _prompts(cfg, (4, 6), seed=3)
    ref = ServingEngine(cfg, params, mesh, max_len=64)
    first = ref.generate([Request(p0, max_new_tokens=8)])[0]
    eos = int(first[0])              # first greedy token of request 0
    reqs = [Request(p0, max_new_tokens=8, eos_id=eos),
            Request(p1, max_new_tokens=4, arrival_time=1.0)]
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=1, max_len=64, prefill_chunk=4))
    outs, summary = eng.run(reqs)
    assert summary["requests_completed"] == 2
    np.testing.assert_array_equal(outs[0], first[:1])   # eos inclusive
    want1 = ref.generate([Request(p1, max_new_tokens=4)])[0]
    np.testing.assert_array_equal(outs[1], want1)
    st = eng.metrics.per_request
    assert st[0].slot == st[1].slot == 0
    assert st[1].admitted >= st[0].finished


@pytest.mark.serving
def test_continuous_streaming_matches_one_shot(setup):
    """Per-request streamed tokens == the run() outputs == the lockstep
    one-shot generate."""
    cfg, params, mesh = setup
    prompts = _prompts(cfg, (6, 4), seed=5)
    streamed = {}

    def on_token(rid, tok):
        streamed.setdefault(rid, []).append(tok)

    reqs = [Request(p, max_new_tokens=5, on_token=on_token) for p in prompts]
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=2, max_len=64, prefill_chunk=0))
    outs, _ = eng.run(reqs)
    ref = ServingEngine(cfg, params, mesh, max_len=64)
    for rid, p in enumerate(prompts):
        want = ref.generate([Request(p, max_new_tokens=5)])[0]
        np.testing.assert_array_equal(np.asarray(streamed[rid], np.int32),
                                      want)
        np.testing.assert_array_equal(outs[rid], want)


@pytest.mark.serving
def test_continuous_kv_regime(setup_softmax):
    """The same scheduler drives the KV-ring regime (softmax backend)."""
    cfg, params, mesh = setup_softmax
    prompts = _prompts(cfg, (5, 3, 6), seed=7)
    reqs = [Request(p, max_new_tokens=4, arrival_time=float(i))
            for i, p in enumerate(prompts)]
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=2, max_len=64, prefill_chunk=4))
    outs, summary = eng.run(reqs)
    assert summary["requests_completed"] == 3
    ref = ServingEngine(cfg, params, mesh, max_len=64)
    for i, p in enumerate(prompts):
        want = ref.generate([Request(p, max_new_tokens=4)])[0]
        np.testing.assert_array_equal(outs[i], want)


@pytest.mark.serving
def test_out_of_order_arrival_not_blocked(setup):
    """A request submitted later but arriving earlier must not be
    head-of-line blocked by an earlier submission with a far-future
    arrival."""
    cfg, params, mesh = setup
    p0, p1 = _prompts(cfg, (4, 5), seed=11)
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=2, max_len=32, prefill_chunk=4))
    eng.submit(Request(p0, max_new_tokens=3, arrival_time=500.0))
    eng.submit(Request(p1, max_new_tokens=3, arrival_time=0.0))
    outs, summary = eng.run(max_ticks=50)
    assert len(outs[1]) == 3                       # rid 1 served immediately
    assert eng.metrics.per_request[1].first_token < 20
    assert len(outs[0]) == 0                       # rid 0 still waiting


@pytest.mark.serving
def test_engine_metrics_shape(setup):
    cfg, params, mesh = setup
    reqs = [Request(p, max_new_tokens=3)
            for p in _prompts(cfg, (4, 4), seed=9)]
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=2, max_len=32, prefill_chunk=4))
    _, summary = eng.run(reqs)
    for key in ("ticks", "decode_ticks", "prefill_ticks",
                "decode_tokens_per_s", "ttft_ticks_p50", "ttft_ticks_p95",
                "mean_queue_depth", "mean_slot_occupancy"):
        assert key in summary, key
    assert 0.0 <= summary["mean_slot_occupancy"] <= 1.0
    assert summary["tokens_generated"] == 6
    assert summary["ttft_ticks_p50"] is not None
