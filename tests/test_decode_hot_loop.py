"""On-device serving hot loop (decode path).

Covers the PR-3 vertical slice: fused on-device sampling (seeded parity vs
the host oracle, slot-placement invariance), the active-slot mask threaded
through the model decode path (reference-path state passthrough vs the
Pallas active-row oracle, both cache regimes), K-tick macro-stepping
(K=1 vs K>1 token-stream and eviction parity), the length-bucketed masked
prefill fallback, and the host-sync cadence metrics.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ServingConfig
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.models import attention as attn
from repro.serving import sampling
from repro.serving.engine import (ContinuousServingEngine, Request,
                                  ServingEngine)
from sharded_driver import poisson_trace


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_smoke_config("slayformer-124m")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    return cfg, params, mesh


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg.vocab_size, size=n).astype(np.int32)
            for n in lengths]


# ---------------------------------------------------------------------------
# Fused on-device sampling
# ---------------------------------------------------------------------------


@pytest.mark.serving
@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_device_sampler_matches_host(temperature):
    """The fused sampler and the host oracle pick identical tokens for the
    same (seed, rid, idx) keys — greedy and Gumbel."""
    logits = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (5, 91)),
                        np.float32)
    rids = np.array([7, 0, 3, 3, 12], np.int32)
    idxs = np.array([0, 5, 1, 2, 9], np.int32)
    toks = sampling.sample_tokens(jnp.asarray(logits), jnp.asarray(rids),
                                  jnp.asarray(idxs),
                                  temperature=temperature, seed=11)
    for i in range(5):
        want = sampling.host_sample_token(
            logits[i], int(rids[i]), int(idxs[i]),
            temperature=temperature, seed=11)
        assert int(toks[i]) == want


@pytest.mark.serving
def test_sampler_independent_of_slot_placement():
    """Sampling is keyed on (seed, rid, idx) — the same request samples the
    same token regardless of which pool row it occupies or who shares the
    batch (the property that makes K=1 and K>1 streams identical)."""
    row = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (64,)),
                     np.float32)
    batch = np.stack([row, row + 1.0, row])      # rid 5 in slots 0 and 2
    rids = jnp.asarray([5, 1, 5], jnp.int32)
    idxs = jnp.asarray([2, 2, 2], jnp.int32)
    toks = sampling.sample_tokens(jnp.asarray(batch), rids, idxs,
                                  temperature=0.9, seed=0)
    alone = sampling.sample_tokens(jnp.asarray(row[None]),
                                   jnp.asarray([5], jnp.int32),
                                   jnp.asarray([2], jnp.int32),
                                   temperature=0.9, seed=0)
    assert int(toks[0]) == int(toks[2]) == int(alone[0])


# ---------------------------------------------------------------------------
# Masked decode through the model path
# ---------------------------------------------------------------------------


def _leaves_at_slot(cache, slot, batch):
    out = []
    for x in jax.tree.leaves(cache):
        a = np.asarray(x)
        if a.ndim >= 2 and a.shape[1] == batch:   # (nl, B, ...) leaves
            out.append(a[:, slot].copy())
    return out


@pytest.mark.serving
@pytest.mark.parametrize("kind", ["slay", "softmax"])
def test_masked_decode_state_passthrough(kind):
    """Model-path masked decode honours the Pallas kernel contract on both
    cache regimes: drained slots keep every cache byte (incl. pos)
    bit-identical, active slots match the unmasked decode exactly."""
    cfg = configs.get_smoke_config("slayformer-124m", attn_kind=kind)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 7), 0,
                              cfg.vocab_size)
    pool = api.init_cache(cfg, 3, 32)
    _, req = api.prefill(params, cfg, {"tokens": toks}, max_len=32)
    pool = api.write_slot(cfg, pool, req, 0)
    pool = api.write_slot(cfg, pool, req, 2)
    step_tok = jnp.full((3, 1), 5, jnp.int32)
    active = jnp.asarray([True, False, True])

    before_slot1 = _leaves_at_slot(pool, 1, 3)
    lg_m, cache_m = api.decode_step(params, cfg, pool, step_tok, active)
    lg_u, cache_u = api.decode_step(params, cfg, pool, step_tok)

    # Drained slot: every stacked leaf bit-identical, pos frozen.
    after_slot1 = _leaves_at_slot(cache_m, 1, 3)
    for b, a in zip(before_slot1, after_slot1):
        np.testing.assert_array_equal(b, a)
    assert np.asarray(cache_m.pos).tolist() == [8, 0, 8]

    # Active slots: logits and cache match the unmasked decode exactly.
    np.testing.assert_array_equal(np.asarray(lg_m[0]), np.asarray(lg_u[0]))
    np.testing.assert_array_equal(np.asarray(lg_m[2]), np.asarray(lg_u[2]))
    for xm, xu in zip(_leaves_at_slot(cache_m, 0, 3),
                      _leaves_at_slot(cache_u, 0, 3)):
        np.testing.assert_array_equal(xm, xu)


@pytest.mark.serving
@pytest.mark.kernels
def test_masked_reference_matches_pallas_active_row_oracle():
    """attention.decode_step's reference-path masking and the decode
    kernel's active-row semantics (via ops.decode_linear_step, interpret
    kernel + jnp oracle) agree on the constant-state regime."""
    rng = np.random.default_rng(0)
    B, hkv, g, m, dv = 4, 2, 2, 16, 8
    qf = jnp.asarray(rng.standard_normal((B, hkv * g, m)), jnp.float32)
    kf = jnp.asarray(np.abs(rng.standard_normal((B, hkv, m))), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, hkv, dv)), jnp.float32)
    s = jnp.asarray(rng.standard_normal((B, hkv, m, dv)), jnp.float32)
    z = jnp.asarray(np.abs(rng.standard_normal((B, hkv, m))), jnp.float32)
    active = jnp.asarray([1, 0, 1, 0], jnp.int32)

    # Oracle path (jnp reference, active-row masked).
    y_r, s_r, z_r = ops.decode_linear_step(qf, kf, v, s, z, active)
    # Interpret-mode Pallas kernel, same masked semantics.
    y_k, s_k, z_k = ops.decode_linear_step(qf, kf, v, s, z, active,
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(z_k), np.asarray(z_r), atol=1e-5)
    # Drained rows: exact passthrough and zero output on both paths.
    for s2, z2, y2 in ((s_r, z_r, y_r), (s_k, z_k, y_k)):
        np.testing.assert_array_equal(np.asarray(s2[1]), np.asarray(s[1]))
        np.testing.assert_array_equal(np.asarray(z2[3]), np.asarray(z[3]))
        assert np.all(np.asarray(y2[1]) == 0)
        assert np.all(np.asarray(y2[3]) == 0)


@pytest.mark.serving
def test_masked_decode_requires_vector_pos():
    spec = configs.get_smoke_config("slayformer-124m").attention_spec()
    cache = attn.init_cache(spec, (), 1, 4, 4, 8, jnp.float32)
    q = jnp.zeros((2, 4))
    with pytest.raises(ValueError, match="per-slot"):
        attn.decode_step(spec, None, q, q[:1], q[:1], cache,
                         active=jnp.asarray([True]))


def _decode_step_xs_ys(params, cfg, cache, tokens, active):
    """Oracle: the layer scan that slices each layer's linear (S, z) out of
    the stack as scanned ``xs`` and writes it back as ``ys`` — the
    formulation ``transformer.decode_step`` replaced with an in-place
    carry. Covers the dense, local/global and hybrid layer bodies."""
    from repro.models import layers, ssm
    from repro.models import transformer as tf
    x = layers.embed(params["embed"], tokens[:, 0]).astype(
        cfg.activation_dtype)
    pos = cache.pos
    act = active.astype(bool)
    slay_params = params.get("slay")

    def body(x, scanned):
        lp, ac, new = scanned["params"], scanned["attn"], {}
        xa = layers.rmsnorm(lp["pre_attn"], x)
        q, k, v = (jnp.einsum("bd,dhk->bhk", xa, lp["attn"][w])
                   for w in ("wq", "wk", "wv"))
        p1 = pos[:, None]
        q = layers.rope(q[:, None], p1, cfg.rope_theta)[:, 0]
        k = layers.rope(k[:, None], p1, cfg.rope_theta)[:, 0]
        spec_g = cfg.attention_spec(local=False)
        if cfg.local_global_period and cfg.local_window:
            spec_l = cfg.attention_spec(local=True)

            def _local():
                y, c = attn.decode_step(spec_l, None, q, k, v, ac,
                                        active=act)
                return y, tf._merge_cache(ac, c)

            def _global():
                y, c = attn.decode_step(spec_g, slay_params, q, k, v, ac,
                                        active=act)
                return y, tf._merge_cache(ac, c)

            y, new["attn"] = jax.lax.cond(scanned["kind"] == 1, _local,
                                          _global)
        else:
            y, new["attn"] = attn.decode_step(spec_g, slay_params, q, k, v,
                                              ac, active=act)
        a = jnp.einsum("bhk,hkd->bd", y, lp["attn"]["wo"])
        if cfg.family == "hybrid":
            m, st = ssm.ssd_decode_step(
                lp["ssd"], xa, scanned["ssm"], d_state=cfg.ssm_state,
                expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
                ngroups=cfg.ssm_ngroups, conv_width=cfg.ssm_conv_width)
            m = jnp.where(act[:, None], m, 0).astype(m.dtype)
            a = 0.5 * (a + m)
            new["ssm"] = tf._state_passthrough(st, scanned["ssm"], act)
        x = x + a
        xm = layers.rmsnorm(lp["pre_mlp"], x)
        return x + layers.mlp(lp["mlp"], xm, cfg.gated_mlp), new

    scanned = {"params": params["layers"], "attn": cache.attn,
               "kind": jnp.asarray(tf._layer_kinds(cfg))}
    if cache.ssm is not None:
        scanned["ssm"] = cache.ssm
    x, new = jax.lax.scan(body, x, scanned)
    x = layers.rmsnorm(params["final_norm"], x)
    logits = layers.unembed(params.get("unembed", params["embed"]), x,
                            cfg.final_logit_softcap)
    return logits[:, None, :], tf.DecodeCache(
        new["attn"], new.get("ssm"), pos + act.astype(jnp.int32),
        cache.pages)


@pytest.mark.serving
@pytest.mark.parametrize("arch,use_pallas", [
    ("slayformer-124m", True), ("slayformer-124m", False),
    ("gemma2-27b", True), ("hymba-1.5b", True)])
def test_decode_step_carry_matches_xs_ys_oracle(arch, use_pallas):
    """The layer scan's in-place (S, z) carry is the old per-layer
    slice/write-back formulation, bit for bit: logits and every cache leaf
    over 3 masked ticks, for the dense, local/global (``lax.cond``) and
    hybrid layer bodies, on the kernel's oracle and on the jnp path."""
    cfg = configs.get_smoke_config(arch, use_pallas=use_pallas)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    pool = api.init_cache(cfg, 3, 32)
    for slot, n in ((0, 7), (2, 5)):
        toks = jax.random.randint(jax.random.PRNGKey(slot + 1), (1, n), 0,
                                  cfg.vocab_size)
        _, req = api.prefill(params, cfg, {"tokens": toks}, max_len=32)
        pool = api.write_slot(cfg, pool, req, slot)
    assert pool.attn.s is not None        # the carry path is the one taken
    new = jax.jit(lambda c, t, a: api.decode_step(params, cfg, c, t, a))
    old = jax.jit(lambda c, t, a: _decode_step_xs_ys(params, cfg, c, t, a))
    got = want = pool
    for tick, live in enumerate(([1, 0, 1], [1, 1, 0], [0, 1, 1])):
        tok = jnp.full((3, 1), 5 + tick, jnp.int32)
        act = jnp.asarray(live, bool)
        lg_got, got = new(got, tok, act)
        lg_want, want = old(want, tok, act)
        np.testing.assert_array_equal(np.asarray(lg_got), np.asarray(lg_want))
        assert (jax.tree.structure(got) == jax.tree.structure(want))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _one_hot_rows(stack, rows, layer, at, live):
    """Reference ring write: the select over every row of the layer's ring
    that ``attention._write_rows`` replaced. Row ``at[b]`` of slot b takes
    ``rows[b]`` where ``live[b]`` holds; the layer's whole ring is read
    and written back."""
    ring = stack[layer]                                   # (B, S, Hkv, d)
    write = jnp.arange(ring.shape[1])[None, :] == at[:, None]
    if live is not None:
        write = write & live[:, None]
    new = jnp.where(write[:, :, None, None],
                    rows.astype(ring.dtype)[:, None], ring)
    return stack.at[layer].set(new)


RING_CFGS = {
    "softmax": lambda: configs.get_smoke_config("slayformer-124m",
                                                attn_kind="softmax"),
    "yat": lambda: configs.get_smoke_config("slayformer-124m",
                                            attn_kind="yat"),
    "mixed": lambda: configs.ArchConfig(
        name="ring-mixed", family="decoder", num_layers=3, d_model=32,
        num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64,
        attn_kind="softmax", layer_types="attention,mamba,attention",
        ssm_state=8, ssm_head_dim=8, chunk_size=8),
}


def ring_parity(cfg, mesh, *, slots=4, max_len=16, ticks=40, seed=0):
    """Decode ``ticks`` ticks with random active masks over a pool of
    ``slots`` rings of ``max_len`` rows, sharded over ``mesh``'s slot axis,
    from prompts that leave every slot's position to wrap past
    ``max_len``. The in-place row write must give the rings, every other
    cache leaf and the logits of the one-hot reference (one device, the
    layer scan through ``xs``/``ys`` for a homogeneous stack) bit for
    bit. Returns the sharded decode step's compiled text."""
    from repro.distributed import sharding as shd

    params = api.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    pool = api.init_cache(cfg, slots, max_len)
    for slot in range(slots):
        n = int(rng.integers(1, max_len - 2))
        toks = jnp.asarray(rng.integers(3, cfg.vocab_size, (1, n)), jnp.int32)
        _, req = api.prefill(params, cfg, {"tokens": toks}, max_len=max_len)
        pool = api.write_slot(cfg, pool, req, slot)
    axes, _ = shd.pool_slot_axes(mesh, shd.DEFAULT_RULES, slots)
    c_sh = shd.serving_cache_sharding(mesh, shd.DEFAULT_RULES, pool,
                                      num_slots=slots)
    v_sh = shd.serving_vector_sharding(mesh, num_slots=slots)

    def step(cache, tok, act):
        return api.decode_step(params, cfg, cache, tok[:, None], act)

    new = jax.jit(shd.in_slot_pool(step, mesh, axes),
                  in_shardings=(c_sh, v_sh, v_sh))
    mixed = bool(cfg.layer_types)

    def old(cache, tok, act):
        with pytest.MonkeyPatch.context() as m:
            m.setattr(attn, "_write_rows", _one_hot_rows)
            if mixed:
                return jax.jit(step)(cache, tok, act)
            return jax.jit(_decode_step_xs_ys, static_argnums=1)(
                params, cfg, cache, tok[:, None], act)

    with mesh:
        got = jax.device_put(pool, c_sh)
    want = pool
    for _ in range(ticks):
        tok = jnp.asarray(rng.integers(3, cfg.vocab_size, slots), jnp.int32)
        act = jnp.asarray(rng.random(slots) < 0.7)
        lg_got, got = new(got, tok, act)
        lg_want, want = old(want, tok, act)
        np.testing.assert_array_equal(np.asarray(lg_got), np.asarray(lg_want))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(np.min(np.asarray(got.pos))) > max_len     # every ring wrapped
    return new.lower(got, tok, act).compile().as_text()


@pytest.mark.serving
@pytest.mark.parametrize("kind", sorted(RING_CFGS))
def test_ring_row_write_matches_one_hot_select(kind):
    """One row per live slot, written in place, is the one-hot select over
    the whole ring, bit for bit, on a one-device pool (the 2-shard pool:
    ``tests/sharded_driver.py --check ring``)."""
    ring_parity(RING_CFGS[kind](), make_host_mesh())


# ---------------------------------------------------------------------------
# K-tick macro-stepping
# ---------------------------------------------------------------------------


@pytest.mark.serving
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_macro_step_vs_per_tick_parity(setup, temperature):
    """K=8 and K=1 engines emit byte-identical per-request token streams
    (greedy and sampled), complete the same requests, and preserve the
    slot-reuse/eviction invariant."""
    cfg, params, mesh = setup
    prompts = _prompts(cfg, (5, 9, 3, 7), seed=2)

    def run(K):
        reqs = [Request(p, max_new_tokens=6, arrival_time=float(2 * i))
                for i, p in enumerate(prompts)]
        eng = ContinuousServingEngine(
            cfg, params, mesh,
            serving=ServingConfig(num_slots=2, max_len=64, prefill_chunk=4,
                                  macro_ticks=K, temperature=temperature,
                                  seed=13))
        outs, summary = eng.run(reqs)
        return eng, outs, summary

    eng8, outs8, sum8 = run(8)
    eng1, outs1, sum1 = run(1)
    assert sum8["requests_completed"] == sum1["requests_completed"] == 4
    for rid in outs1:
        np.testing.assert_array_equal(outs8[rid], outs1[rid])
    # Dispatch amortization actually happened under K=8.
    assert sum8["decode_dispatches"] < sum1["decode_dispatches"]
    assert sum8["dispatches_per_decode_tick"] <= 1.0
    # Eviction invariant holds under macro-stepping: a slot's next tenant
    # is admitted no earlier than the previous tenant finished.
    for eng in (eng8, eng1):
        by_slot = {}
        for st in eng.metrics.per_request.values():
            by_slot.setdefault(st.slot, []).append(st)
        for tenants in by_slot.values():
            tenants.sort(key=lambda s: s.admitted)
            for prev, nxt in zip(tenants, tenants[1:]):
                assert nxt.admitted >= prev.finished


@pytest.mark.serving
def test_macro_step_eos_mid_buffer(setup):
    """A slot hitting EOS mid-macro-step is masked on device for the
    remaining ticks: nothing is emitted past EOS and the slot is reused."""
    cfg, params, mesh = setup
    p0, p1 = _prompts(cfg, (4, 6), seed=3)
    ref = ServingEngine(cfg, params, mesh, max_len=64)
    first = ref.generate([Request(p0, max_new_tokens=8)])[0]
    # EOS = a greedy token whose *first* occurrence is past the prefill
    # token, so the stop happens inside the macro-step buffer.
    eos, cut = int(first[0]), 0
    for i in range(1, len(first)):
        if first[i] not in first[:i]:
            eos, cut = int(first[i]), i
            break
    reqs = [Request(p0, max_new_tokens=8, eos_id=eos),
            Request(p1, max_new_tokens=4, arrival_time=1.0)]
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=1, max_len=64, prefill_chunk=4,
                              macro_ticks=8))
    outs, summary = eng.run(reqs)
    assert summary["requests_completed"] == 2
    np.testing.assert_array_equal(outs[0], first[:cut + 1])  # eos inclusive
    want1 = ref.generate([Request(p1, max_new_tokens=4)])[0]
    np.testing.assert_array_equal(outs[1], want1)
    st = eng.metrics.per_request
    assert st[0].slot == st[1].slot == 0
    assert st[1].admitted >= st[0].finished


@pytest.mark.serving
def test_macro_streaming_and_ttft_per_tick(setup):
    """Streaming callbacks fire per replayed tick with exact tick-granular
    TTFT — not once per host sync."""
    cfg, params, mesh = setup
    prompts = _prompts(cfg, (6, 4), seed=5)
    seen = {}

    def on_token(rid, tok):
        seen.setdefault(rid, []).append(tok)

    reqs = [Request(p, max_new_tokens=5, on_token=on_token)
            for p in prompts]
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=2, max_len=64, prefill_chunk=4,
                              macro_ticks=8))
    outs, summary = eng.run(reqs)
    for rid, p in enumerate(prompts):
        np.testing.assert_array_equal(np.asarray(seen[rid], np.int32),
                                      outs[rid])
    # TTFT is recorded at the (prefill) tick the first token was emitted,
    # so it is well-defined and tick-exact under macro-stepping.
    for st in eng.metrics.per_request.values():
        assert st.ttft_ticks is not None and st.ttft_ticks >= 0
    # Per-tick accounting: replayed decode ticks count individually (more
    # ticks than dispatches), and the tick clock covers every decode tick
    # — metrics were sampled per replayed tick, not per host sync.
    assert summary["decode_ticks"] > summary["decode_dispatches"]
    assert summary["ticks"] >= (summary["prefill_ticks"]
                                + summary["decode_ticks"])


# ---------------------------------------------------------------------------
# Length-bucketed masked prefill fallback
# ---------------------------------------------------------------------------


@pytest.mark.serving
def test_masked_prefill_matches_unpadded():
    """Right-padded prefill with true_len reproduces the unpadded prefill:
    same last-token logits, same decode continuation, same pos."""
    cfg = configs.get_smoke_config("slayformer-124m",
                                   attn_kind="yat_spherical")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 7), 3,
                              cfg.vocab_size)
    lg_u, cache_u = api.prefill(params, cfg, {"tokens": toks}, max_len=32)
    padded = jnp.pad(toks, ((0, 0), (0, 9)))             # 7 -> 16 bucket
    lg_m, cache_m = api.prefill(params, cfg, {"tokens": padded},
                                max_len=32,
                                true_len=jnp.asarray([7], jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_m, np.float32),
                               np.asarray(lg_u, np.float32), atol=1e-4)
    assert np.asarray(cache_m.pos).tolist() == [7]
    tok = jnp.argmax(lg_u[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(3):
        l_u, cache_u = api.decode_step(params, cfg, cache_u, tok)
        l_m, cache_m = api.decode_step(params, cfg, cache_m, tok)
        np.testing.assert_allclose(np.asarray(l_m, np.float32),
                                   np.asarray(l_u, np.float32), atol=1e-4)
        tok = jnp.argmax(l_u[:, -1], -1).astype(jnp.int32)[:, None]


@pytest.mark.serving
def test_bucketed_fallback_parity_and_metrics(setup):
    """The bucketed masked-prefill fallback still serves exactly via pow-2
    buckets: token parity with the lockstep oracle, one compile per
    bucket, and hit/miss counts exposed in the engine metrics. Exact-yat
    kinds chunk by default now (DESIGN.md §9), so the fallback is routed
    explicitly with prefill_chunk=0."""
    cfg = configs.get_smoke_config("slayformer-124m",
                                   attn_kind="yat_spherical")
    assert api.supports_chunked_prefill(cfg)     # fallback retired for yat
    assert api.supports_masked_prefill(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    mesh = setup[2]
    prompts = _prompts(cfg, (5, 9, 3, 12), seed=4)   # buckets 16,16,16,16
    reqs = [Request(p, max_new_tokens=4, arrival_time=float(i))
            for i, p in enumerate(prompts)]
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=2, max_len=64, prefill_chunk=0,
                              macro_ticks=4))
    outs, summary = eng.run(reqs)
    assert summary["requests_completed"] == 4
    assert summary["bucket_misses"] == 1        # single pow-2 bucket: 16
    assert summary["bucket_hits"] == 3
    assert eng.jit_cache_entries()["prefill_masked"] == 1
    ref = ServingEngine(cfg, params, mesh, max_len=64)
    for i, p in enumerate(prompts):
        want = ref.generate([Request(p, max_new_tokens=4)])[0]
        np.testing.assert_array_equal(outs[i], want)


@pytest.mark.serving
def test_masked_prefill_unsupported_families_raise():
    cfg = configs.get_smoke_config("mamba2-780m")
    assert not api.supports_masked_prefill(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError):
        api.prefill(params, cfg, {"tokens": toks}, max_len=32,
                    true_len=jnp.asarray([4], jnp.int32))


# ---------------------------------------------------------------------------
# Host-sync cadence metrics
# ---------------------------------------------------------------------------


# Every decode regime the engine serves, as (arch, config overrides,
# ServingConfig overrides, trace seed). Four Poisson requests (prompts of
# 3-8 tokens, 16 new tokens each) on a 2-slot pool at K=8; the exact-yat
# pair replays the speculative contract trace (seed 10, tie-free argmaxes).
_YAT = {"attn_kind": "yat_spherical", "slay_anchors": 16, "slay_prf": 32}
_CADENCE_REGIMES = {
    "constant_state": ("slayformer-124m", {"attn_kind": "slay"}, {}, 1234),
    "kv_ring": ("slayformer-124m", {"attn_kind": "softmax"}, {}, 1234),
    "kv_ring_paged": ("slayformer-124m", {"attn_kind": "softmax"},
                      {"page_size": 8}, 1234),
    "exact_yat": ("slayformer-124m", _YAT, {}, 10),
    "ssm_scan": ("mamba2-780m", {}, {}, 1234),
    "hybrid_scan": ("hymba-1.5b", {}, {}, 1234),
    "speculative": ("slayformer-124m", _YAT,
                    {"speculative": True, "spec_gamma": 2}, 10),
}


@functools.lru_cache(maxsize=None)
def _model(arch, overrides):
    cfg = configs.get_smoke_config(arch, **dict(overrides))
    return cfg, api.init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.serving
@pytest.mark.parametrize("regime,load", [
    ("constant_state", 0.25), ("constant_state", 1.0), ("kv_ring", 0.25),
    ("kv_ring", 1.0), ("kv_ring_paged", 1.0), ("exact_yat", 1.0),
    ("ssm_scan", 1.0), ("hybrid_scan", 1.0), ("speculative", 1.0),
], ids=lambda v: v if isinstance(v, str) else f"load{v:g}")
def test_host_sync_cadence_contract(setup, regime, load):
    """With K=8 and enough decode work, the decode loop syncs to host at
    most once per 8 generated tokens, dispatches once per pool (never per
    slot), and the hot loop (the macro-step, or the speculative macro-step)
    stays a single jit cache entry within a budget of 24 live entries, in
    every decode regime; the pool drains to zero occupancy and queue
    depth. The scan-carry families prefill chunk by chunk, never through
    the bucketed fallback."""
    mesh = setup[2]
    arch, overrides, serving_kw, seed = _CADENCE_REGIMES[regime]
    cfg, params = _model(arch, tuple(sorted(overrides.items())))
    if regime in ("ssm_scan", "hybrid_scan"):
        assert api.supports_chunked_prefill(cfg), arch
    reqs = poisson_trace(np.random.default_rng(seed), 4, load, (3, 8),
                         cfg.vocab_size, 16)
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=2, max_len=32, prefill_chunk=4,
                              macro_ticks=8, **serving_kw))
    _, summary = eng.run(reqs)
    assert summary["requests_completed"] == 4
    assert summary["host_syncs_per_token"] <= 1.0 / 8 + 1e-9
    assert summary["tokens_per_dispatch"] >= 8.0
    assert summary["dispatches_per_decode_tick"] <= 1.0
    assert summary["final_occupancy"] == 0
    assert summary["final_queue_depth"] == 0
    entries = eng.jit_cache_entries()
    hot = "spec_macro" if regime == "speculative" else "macro_decode"
    assert entries[hot] == 1, entries
    assert entries["sample"] == 1
    assert sum(v for v in entries.values() if v > 0) <= 24, entries
    if regime in ("ssm_scan", "hybrid_scan"):
        assert summary["bucket_misses"] == 0 == summary["bucket_hits"]
        assert summary["prefill_ticks"] > 0
