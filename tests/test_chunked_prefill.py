"""Exact chunked-prefill continuation for the scan-carry families and the
exact quadratic yat kinds (DESIGN.md §9): SSD ragged-tail regression vs a
loop oracle, chunked-vs-whole-prompt parity across ragged chunk schedules
for ssm/hybrid and yat, serving-engine stream equality between the new
chunked path and the retired bucketed fallback, and the admission-time
vision-prefix capacity rules — bounded rings still reject oversized
prompts, while unbounded (linear) vision configs absorb the patch prefix
chunk-by-chunk instead (DESIGN.md §11)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ServingConfig
from repro.launch.mesh import make_host_mesh
from repro.models import api, ssm
from repro.models.layers import realize
from repro.serving.engine import (ContinuousServingEngine, Request,
                                  ServingEngine)

# The ISSUE's two ragged schedules (prompt length 529) scaled down by 16x
# for the per-arch engine tests; the SSD unit tests use the full lengths.
_SCHEDULES = ([256, 256, 17], [129, 400])


def _ssd_kwargs():
    return dict(d_state=8, expand=2, head_dim=8, ngroups=1, conv_width=4)


def _ssd_params(key, d_model=16):
    kw = _ssd_kwargs()
    specs = ssm.ssd_specs(d_model, kw["d_state"], kw["expand"],
                          kw["head_dim"], kw["ngroups"], kw["conv_width"])
    return realize(specs, key, jnp.float32), kw


# ---------------------------------------------------------------------------
# SSD unit level
# ---------------------------------------------------------------------------


def test_ssd_chunked_ragged_tail_matches_loop_oracle(key):
    """Regression: L=257 with chunk=256 used to raise ValueError in
    _ssd_chunked; the zero-padded (dt=0) tail must match the per-token
    decode recurrence exactly."""
    params, kw = _ssd_params(key)
    B, L = 2, 257
    x = jax.random.normal(jax.random.PRNGKey(1), (B, L, 16)) * 0.3
    y_full = ssm.ssd_forward(params, x, chunk_size=256, **kw)
    state = ssm.ssd_init_state((B,), 16, kw["d_state"], kw["expand"],
                               kw["head_dim"], kw["ngroups"],
                               kw["conv_width"])

    def step(st, xt):
        y, st = ssm.ssd_decode_step(params, xt, st, **kw)
        return st, y

    _, y_dec = jax.lax.scan(step, state, jnp.moveaxis(x, 1, 0))
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(y_dec, 0, 1)),
                               np.asarray(y_full), atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("schedule", _SCHEDULES, ids=["256-256-17",
                                                      "129-400"])
def test_ssd_prefill_chunk_schedule_invariant(key, schedule):
    """ssd_prefill_chunk absorbed chunk-by-chunk reproduces the whole-
    sequence forward (outputs) and a one-shot absorption (final scan state
    + conv tail), for ragged schedules."""
    params, kw = _ssd_params(key)
    B, L = 1, sum(schedule)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, L, 16)) * 0.3
    y_full = ssm.ssd_forward(params, x, chunk_size=64, **kw)
    st = ssm.ssd_init_state((B,), 16, kw["d_state"], kw["expand"],
                            kw["head_dim"], kw["ngroups"], kw["conv_width"])
    ys, lo = [], 0
    for n in schedule:
        y, st = ssm.ssd_prefill_chunk(params, x[:, lo:lo + n], st,
                                      chunk_size=64, **kw)
        ys.append(y)
        lo += n
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 1)),
                               np.asarray(y_full), atol=1e-5, rtol=1e-4)
    st_one = ssm.ssd_init_state((B,), 16, kw["d_state"], kw["expand"],
                                kw["head_dim"], kw["ngroups"],
                                kw["conv_width"])
    _, st_one = ssm.ssd_prefill_chunk(params, x, st_one, chunk_size=64,
                                      **kw)
    np.testing.assert_allclose(np.asarray(st.h), np.asarray(st_one.h),
                               atol=1e-5, rtol=1e-4)
    # The conv tail is the last W-1 rows of the in_proj matmul. XLA's CPU
    # dot blocks by the row count, so a row projected in a 17-row chunk
    # and in a 529-row one-shot call can round differently in its
    # 16-term fp32 sum (measured: 1.2e-7, one ulp at |u| < 1). Everything
    # downstream of the tail is exact; a few ulps are allowed here.
    np.testing.assert_allclose(np.asarray(st.conv), np.asarray(st_one.conv),
                               atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# Model level: chunked vs whole-prompt prefill
# ---------------------------------------------------------------------------


def _chunk_parity(cfg, schedule, atol=5e-3):
    """fp32 activations so the check is tight: the continuation is exact
    math, and only fp summation order differs between schedules (bf16
    engine streams are covered token-exactly by the engine tests)."""
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    L = sum(schedule)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, L), 3,
                              cfg.vocab_size)
    lg_full, cache_full = api.prefill(params, cfg, {"tokens": toks},
                                      max_len=L + 16)
    cache = api.init_cache(cfg, 1, L + 16)
    lo = 0
    for n in schedule:
        lg, cache = api.prefill_chunk(cfg, params, cache,
                                      toks[:, lo:lo + n])
        lo += n
    np.testing.assert_allclose(np.asarray(lg, np.float32),
                               np.asarray(lg_full, np.float32), atol=atol)
    assert np.asarray(cache.pos).tolist() == [L]
    tok = jnp.argmax(lg_full[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(3):
        l1, cache_full = api.decode_step(params, cfg, cache_full, tok)
        l2, cache = api.decode_step(params, cfg, cache, tok)
        np.testing.assert_allclose(np.asarray(l2, np.float32),
                                   np.asarray(l1, np.float32), atol=atol)
        tok = jnp.argmax(l1[:, -1], -1).astype(jnp.int32)[:, None]


@pytest.mark.serving
@pytest.mark.parametrize("schedule", ([16, 16, 2], [9, 25]),
                         ids=["16-16-2", "9-25"])
@pytest.mark.parametrize("arch", ["mamba2-780m", "hymba-1.5b"])
def test_scan_carry_chunked_prefill_parity(arch, schedule):
    """ssm/hybrid: chunk-by-chunk prefill == whole-prompt prefill (logits,
    pos, decode continuation) across ragged chunk schedules."""
    cfg = configs.get_smoke_config(arch, dtype="float32")
    assert api.supports_chunked_prefill(cfg)
    _chunk_parity(cfg, schedule)


@pytest.mark.serving
@pytest.mark.parametrize("kind", ["yat", "yat_spherical"])
def test_exact_yat_chunked_prefill_parity(kind):
    """Exact quadratic yat kinds: ring-prefix continuation == whole-prompt
    prefill."""
    cfg = configs.get_smoke_config("slayformer-124m", attn_kind=kind,
                                   dtype="float32")
    assert api.supports_chunked_prefill(cfg)
    _chunk_parity(cfg, [4, 5, 2])


@pytest.mark.serving
def test_hybrid_kv_ring_chunked_prefill_parity():
    """Hybrid with a KV-ring attention backend (softmax) chunks exactly
    too — both carries (KV ring + SSD scan state) cross chunk bounds."""
    cfg = configs.get_smoke_config("hymba-1.5b", attn_kind="softmax",
                                   dtype="float32")
    assert api.supports_chunked_prefill(cfg)
    _chunk_parity(cfg, [7, 12, 3])


@pytest.mark.serving
def test_prefill_chunk_gate_errors_name_the_gate():
    """Only encdec still gates chunked prefill, and its error names the
    family. Vision decoders chunk now — the patch prefix feeds through
    ``prefill_chunk(embeds=)`` (DESIGN.md §11)."""
    cfg = configs.get_smoke_config("internvl2-76b")
    assert api.supports_chunked_prefill(cfg)

    wcfg = configs.get_smoke_config("whisper-small")
    assert not api.supports_chunked_prefill(wcfg)
    with pytest.raises(NotImplementedError, match="family='encdec'"):
        api.prefill_chunk(wcfg, None, None, jnp.zeros((1, 4), jnp.int32))


@pytest.mark.serving
def test_every_decoder_only_config_is_chunkable():
    """Acceptance: supports_chunked_prefill is True for every decoder-only
    config (ssm, hybrid, every attn kind, vision frontends); only encdec
    falls back."""
    for name in configs.ALL_ARCHS:
        cfg = configs.get_smoke_config(name)
        want = cfg.family != "encdec"
        assert api.supports_chunked_prefill(cfg) == want, name
    for kind in ("slay", "softmax", "yat", "yat_spherical", "favor",
                 "elu1", "cosformer"):
        cfg = configs.get_smoke_config("slayformer-124m", attn_kind=kind)
        assert api.supports_chunked_prefill(cfg), kind


# ---------------------------------------------------------------------------
# Serving engine level
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg.vocab_size, size=n).astype(np.int32)
            for n in lengths]


@pytest.mark.serving
@pytest.mark.parametrize("arch", ["mamba2-780m", "hymba-1.5b"])
def test_engine_scan_carry_stream_parity(arch, mesh):
    """Continuous engine serves ssm/hybrid via chunked prefill (no bucketed
    fallback: bucket counters stay zero) with lockstep stream parity."""
    cfg = configs.get_smoke_config(arch)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg, (5, 9, 3), seed=1)
    reqs = [Request(p, max_new_tokens=5, arrival_time=float(i))
            for i, p in enumerate(prompts)]
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=2, max_len=64, prefill_chunk=4,
                              macro_ticks=4))
    outs, summary = eng.run(reqs)
    assert summary["requests_completed"] == 3
    assert summary["bucket_misses"] == 0 == summary["bucket_hits"]
    assert summary["prefill_ticks"] > 3          # chunked: > 1 per request
    ref = ServingEngine(cfg, params, mesh, max_len=64)
    for i, p in enumerate(prompts):
        want = ref.generate([Request(p, max_new_tokens=5)])[0]
        np.testing.assert_array_equal(outs[i], want)


@pytest.mark.serving
def test_engine_yat_chunked_vs_bucketed_fallback_streams(mesh):
    """Same requests through the new chunked path and the (retired-for-
    default) bucketed masked-prefill fallback produce identical token
    streams — the fallback was masking nothing but compile granularity."""
    cfg = configs.get_smoke_config("slayformer-124m",
                                   attn_kind="yat_spherical")
    assert api.supports_chunked_prefill(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg, (5, 9, 3, 12), seed=2)

    def run(prefill_chunk):
        # prefill_chunk=0 disables the chunked path, so the engine routes
        # through the pow-2 bucketed masked prefill (the old fallback).
        reqs = [Request(p, max_new_tokens=4, arrival_time=float(i))
                for i, p in enumerate(prompts)]
        eng = ContinuousServingEngine(
            cfg, params, mesh,
            serving=ServingConfig(num_slots=2, max_len=64,
                                  prefill_chunk=prefill_chunk,
                                  macro_ticks=4))
        return eng.run(reqs)

    outs_c, sum_c = run(prefill_chunk=4)
    outs_b, sum_b = run(prefill_chunk=0)
    assert sum_c["bucket_misses"] == 0 and sum_c["prefill_ticks"] > 4
    assert sum_b["bucket_misses"] >= 1           # fallback exercised
    for rid in outs_b:
        np.testing.assert_array_equal(outs_c[rid], outs_b[rid])


@pytest.mark.serving
def test_vision_prefix_cap_rejected_at_admission(mesh):
    """A prompt that fits max_len alone but not with the vision patch
    prefix must be rejected at submit() when the ring is bounded (softmax
    backend) — previously the padded bucket slice silently dropped the
    prompt tail."""
    cfg = configs.get_smoke_config("internvl2-76b",
                                   attn_kind="softmax")  # num_patches=8
    assert api.context_capacity(cfg, 32) is not None
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=1, max_len=32, prefill_chunk=4))
    over = np.ones(32 - 4 - cfg.num_patches + 1, np.int32)  # 1 over budget
    with pytest.raises(ValueError, match="vision-prefix"):
        eng.submit(Request(over, max_new_tokens=4))
    # At the budget it admits and serves.
    fit = np.ones(32 - 4 - cfg.num_patches, np.int32)
    outs, summary = eng.run([Request(fit, max_new_tokens=4)])
    assert summary["requests_completed"] == 1
    assert len(outs[0]) == 4


@pytest.mark.serving
def test_oversized_vision_prompt_served_by_chunked_absorption(mesh):
    """Regression (DESIGN.md §11): the same over-budget request on the
    *linear* backend (constant-state: capacity unbounded) used to be
    rejected too; it now admits, absorbs the patch prefix + prompt
    chunk-by-chunk, and streams exactly what a roomy lockstep reference
    produces. Without chunked prefill the one-shot fallback still cannot
    exceed the ring, so admission keeps rejecting there."""
    cfg = configs.get_smoke_config("internvl2-76b")   # slay: linear
    assert api.context_capacity(cfg, 32) is None
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    over = np.ones(32 - 4 - cfg.num_patches + 1, np.int32)  # 1 over budget
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=1, max_len=32, prefill_chunk=4))
    outs, summary = eng.run([Request(over, max_new_tokens=4)])
    assert summary["requests_completed"] == 1
    ref = ServingEngine(cfg, params, mesh, max_len=64)
    want = ref.generate([Request(over, max_new_tokens=4)])[0]
    np.testing.assert_array_equal(outs[0], want)
    # Chunked prefill is what makes the unbounded admission safe: with it
    # disabled the full-length one-shot prefill would overflow the ring.
    eng0 = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=1, max_len=32, prefill_chunk=0))
    with pytest.raises(ValueError, match="vision-prefix"):
        eng0.submit(Request(over, max_new_tokens=4))
