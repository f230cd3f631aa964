"""Speculative decoding (DESIGN.md §13): the statistical sampling-contract
harness plus the engine-level byte-identity / invariance / rollback suite.

The contract under test is the one the module docstring of
``repro.serving.speculative`` states:

* the accept/resample correction makes the emitted-token distribution
  equal the *verifier's* softmax exactly, for any draft distribution —
  checked empirically with a chi-square bound over randomized
  (logits, temperature) pairs at fixed seeds;
* greedy speculative streams are byte-identical to greedy exact decode;
* accepted streams are placement-, K-, and (greedy) gamma-invariant;
* rejected-suffix rollback composes with paged KV (zero leaked pages),
  the write-ahead journal (only accepted tokens are journaled, so
  crash/restore reproduces streams byte-identically) and NaN quarantine.

Every random draw in this module is seeded; the conftest guard enforces
that repo-wide.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ServingConfig
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.serving import faults
from repro.serving import journal as journal_lib
from repro.serving import sampling, speculative
from repro.serving.engine import ContinuousServingEngine, Request
from sharded_driver import poisson_trace

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------------
# Statistical sampling contract (pure math, no engine)
# ---------------------------------------------------------------------------

# chi-square critical values at p = 0.001 for df = vocab - 1; a correct
# sampler fails a single test with probability 1e-3, and the seeds below
# are fixed, so CI is deterministic: these cases are known-passing draws.
_CHI2_CRIT = {7: 24.32, 15: 37.70}


def _emitted(p_logits, q_logits, *, temperature, seed, n, idx):
    """Simulate n independent speculative draws of one token position:
    draft from q, accept/resample against p. Trials are vectorized over
    the rid axis — by the determinism contract each (seed, rid, idx) is
    an independent stream, which is exactly what the harness needs."""
    vocab = p_logits.shape[-1]
    rids = jnp.arange(n, dtype=jnp.int32)
    idxs = jnp.full((n,), idx, jnp.int32)
    p = jnp.broadcast_to(p_logits, (n, vocab))
    q = jnp.broadcast_to(q_logits, (n, vocab))
    drafts = speculative.draft_sample(q, rids, idxs,
                                      temperature=temperature, seed=seed)
    acc, corr = speculative.accept_and_correct(
        p, q, drafts, rids, idxs, temperature=temperature, seed=seed)
    return np.asarray(jnp.where(acc, drafts, corr)), np.asarray(acc)


def _chi2(counts, probs, n):
    exp = probs * n
    return float(np.sum((counts - exp) ** 2 / np.maximum(exp, 1e-12)))


@pytest.mark.parametrize("case", range(4))
def test_accepted_distribution_matches_verifier(case):
    """Empirical emitted-token histogram ~ softmax(p / T) regardless of
    how far the draft q is from the verifier p (chi-square, p = 0.001)."""
    rng = np.random.default_rng(100 + case)
    vocab = int(rng.choice([8, 16]))
    temperature = float(rng.uniform(0.4, 1.6))
    scale = float(rng.uniform(0.5, 3.0))          # draft/verifier mismatch
    p_logits = jnp.asarray(rng.normal(size=vocab), jnp.float32)
    q_logits = jnp.asarray(rng.normal(size=vocab) * scale, jnp.float32)
    n = 8000
    toks, _ = _emitted(p_logits, q_logits, temperature=temperature,
                       seed=case, n=n, idx=3 + case)
    counts = np.bincount(toks, minlength=vocab)
    probs = np.asarray(jax.nn.softmax(p_logits / temperature))
    assert _chi2(counts, probs, n) < _CHI2_CRIT[vocab - 1]


def test_identical_distributions_always_accept():
    """p == q makes acceptance certain (u * q(d) < p(d) with u in [0, 1))
    — the division-free accept test must not lose this exactness."""
    rng = np.random.default_rng(11)
    logits = jnp.asarray(rng.normal(size=16), jnp.float32)
    toks, acc = _emitted(logits, logits, temperature=0.9, seed=7,
                         n=2000, idx=5)
    assert acc.all()
    counts = np.bincount(toks, minlength=16)
    probs = np.asarray(jax.nn.softmax(logits / 0.9))
    assert _chi2(counts, probs, 2000) < _CHI2_CRIT[15]


def test_greedy_accept_is_verifier_argmax():
    """T = 0: accept iff draft == argmax(p); corrected token is that
    argmax, so the emitted token is the verifier argmax either way."""
    rng = np.random.default_rng(2)
    p = jnp.asarray(rng.normal(size=(6, 32)), jnp.float32)
    top = jnp.argmax(p, axis=-1).astype(jnp.int32)
    drafts = top.at[0].set((top[0] + 1) % 32)     # one wrong proposal
    rids = jnp.arange(6, dtype=jnp.int32)
    idxs = jnp.zeros((6,), jnp.int32)
    acc, corr = speculative.accept_and_correct(
        p, p, drafts, rids, idxs, temperature=0.0, seed=0)
    assert not bool(acc[0]) and bool(jnp.all(acc[1:]))
    assert np.array_equal(np.asarray(corr), np.asarray(top))


def test_substreams_are_independent():
    """DRAFT / ACCEPT / RESAMPLE substreams of one (seed, rid, idx) must
    not collide with each other or with the untagged bonus stream."""
    u = float(sampling.spec_uniform(0, jnp.int32(1), jnp.int32(2)))
    assert 0.0 <= u < 1.0
    rows = [np.asarray(sampling.spec_gumbel_row(0, jnp.int32(1),
                                                jnp.int32(2), tag, 64))
            for tag in (sampling.SPEC_TAG_DRAFT, sampling.SPEC_TAG_RESAMPLE)]
    assert not np.array_equal(rows[0], rows[1])


# ---------------------------------------------------------------------------
# Engine-level contract (byte identity, invariances, rollback)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    # The bench pairing: exact yat_spherical verifier, linear SLAY draft
    # (draft_config swaps attn_kind only; anchors/features shrink the
    # shared trunk so the smoke suite stays fast).
    cfg = configs.get_smoke_config("slayformer-124m",
                                   attn_kind="yat_spherical",
                                   slay_anchors=16, slay_prf=32)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    return cfg, params, mesh


def _trace(cfg):
    rng = np.random.default_rng(7)
    return [Request(rng.integers(1, cfg.vocab_size, size=n).astype(np.int32),
                    max_new_tokens=m, eos_id=1)
            for n, m in [(12, 20), (5, 16), (30, 24), (9, 12)]]


def _sv(**kw):
    return ServingConfig(**{"num_slots": 2, "max_len": 128,
                            "prefill_chunk": 16, "macro_ticks": 4,
                            "debug_audit": True, **kw})


def _run(setup, **kw):
    cfg, params, mesh = setup
    eng = ContinuousServingEngine(cfg, params, mesh, serving=_sv(**kw))
    return eng.run(_trace(cfg))


@pytest.fixture(scope="module")
def greedy_runs(setup):
    ref, s_ref = _run(setup)                                  # plain exact
    spec, s_spec = _run(setup, speculative=True, spec_gamma=2)
    return ref, s_ref, spec, s_spec


@pytest.fixture(scope="module")
def contract_runs(setup):
    """The amortisation contract trace: four Poisson requests (prompts of
    3-8 tokens, 16 new tokens each) on a 2-slot pool at K=8. Greedy byte
    identity presumes a unique fp32 argmax at every emitted position
    (DESIGN.md §13); this seed is checked tie-free."""
    cfg, params, mesh = setup

    def run(**kw):
        reqs = poisson_trace(np.random.default_rng(10), 4, 1.0, (3, 8),
                             cfg.vocab_size, 16)
        eng = ContinuousServingEngine(
            cfg, params, mesh,
            serving=_sv(max_len=32, prefill_chunk=4, macro_ticks=8, **kw))
        return eng.run(reqs)

    ref, s_ref = run()
    spec, s_spec = run(speculative=True, spec_gamma=2)
    return ref, s_ref, spec, s_spec


# (fixture, macro_ticks, least draft acceptance rate) per trace.
_TRACES = {"greedy_runs": (4, 0.0), "contract_runs": (8, 0.5)}


@pytest.mark.parametrize("runs", sorted(_TRACES))
def test_greedy_spec_byte_identical_to_exact(runs, request):
    ref, _, spec, s_spec = request.getfixturevalue(runs)
    assert set(ref) == set(spec)
    for rid in ref:
        assert np.array_equal(ref[rid], spec[rid]), rid
    assert s_spec["requests_completed"] == len(ref)


@pytest.mark.parametrize("runs", sorted(_TRACES))
def test_spec_amortizes_dispatches(runs, request):
    """One speculative dispatch covers K rounds x up to gamma+1 tokens:
    tokens/dispatch must beat both the plain macro engine and the K
    floor, and the acceptance accounting must be populated; on the
    contract trace at least half the drafted tokens are accepted."""
    _, s_ref, _, s_spec = request.getfixturevalue(runs)
    macro_ticks, least_acceptance = _TRACES[runs]
    assert s_spec["tokens_per_dispatch"] > s_ref["tokens_per_dispatch"]
    assert s_spec["tokens_per_dispatch"] > macro_ticks
    assert s_spec["draft_tokens_proposed"] > 0
    assert 0.0 < s_spec["draft_acceptance_rate"] <= 1.0
    assert s_spec["draft_acceptance_rate"] >= least_acceptance
    assert s_spec["speculative"] and s_spec["spec_gamma"] == 2
    assert s_spec["final_occupancy"] == 0


@pytest.mark.parametrize("kw", [
    {"macro_ticks": 1},                 # K-invariance
    {"spec_gamma": 3},                  # greedy gamma-invariance
    {"num_slots": 4},                   # placement invariance
])
def test_greedy_invariance(greedy_runs, setup, kw):
    spec = greedy_runs[2]
    outs, _ = _run(setup, speculative=True,
                   **{"spec_gamma": 2, **kw})
    for rid in spec:
        assert np.array_equal(spec[rid], outs[rid]), (kw, rid)


def test_paged_rollback_leaks_no_pages(greedy_runs, setup):
    """Rejected-suffix rollback on a paged pool: streams unchanged and —
    under the debug audit — every page is back in the free list."""
    spec = greedy_runs[2]
    outs, summ = _run(setup, speculative=True, spec_gamma=2, page_size=16)
    for rid in spec:
        assert np.array_equal(spec[rid], outs[rid]), rid
    assert summ["final_pages_in_use"] == 0


def test_sampled_invariance(setup):
    """T > 0: accepted streams keyed on (seed, rid, token-index) only —
    macro-step size and slot placement must not change them."""
    base, s_base = _run(setup, speculative=True, spec_gamma=2,
                        temperature=0.8, seed=3)
    for kw in ({"macro_ticks": 2}, {"num_slots": 4}):
        outs, _ = _run(setup, speculative=True, spec_gamma=2,
                       temperature=0.8, seed=3, **kw)
        for rid in base:
            assert np.array_equal(base[rid], outs[rid]), (kw, rid)
    assert 0.0 < s_base["draft_acceptance_rate"] < 1.0


# ---------------------------------------------------------------------------
# Composition with the fault-tolerance stack
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_crash_restore_byte_identity(greedy_runs, setup, tmp_path):
    """Journal replay + checkpoint restore under speculative decoding:
    only *accepted* tokens hit the journal, so a mid-flight crash
    restores to byte-identical streams (including the draft pool)."""
    cfg, params, mesh = setup
    ref = greedy_runs[0]
    d = str(tmp_path)
    sv = _sv(speculative=True, spec_gamma=2, checkpoint_every_ticks=6)
    jr = journal_lib.Journal(os.path.join(d, journal_lib.JOURNAL_NAME))
    inj = faults.FaultInjector(crash_window=(9, 9))
    eng = ContinuousServingEngine(cfg, params, mesh, serving=sv,
                                  fault_injector=inj, journal=jr)
    with pytest.raises(faults.EngineCrash):
        eng.run(_trace(cfg))

    eng2 = ContinuousServingEngine.restore(d, cfg, params, mesh, serving=sv)
    assert eng2.recovery["checkpoint_used"]
    outs, _ = eng2.run()
    for rid in ref:
        assert np.array_equal(ref[rid], outs[rid]), rid


@pytest.mark.chaos
def test_quarantine_retry_byte_identity(greedy_runs, setup):
    """NaN-corrupted verifier slots are quarantined at the round fault
    lane; the retried requests still reproduce the reference streams."""
    cfg, params, mesh = setup
    ref = greedy_runs[0]
    inj = faults.FaultInjector(nan_every=8, seed=5)
    eng = ContinuousServingEngine(
        cfg, params, mesh, serving=_sv(speculative=True, spec_gamma=2),
        fault_injector=inj)
    outs, summ = eng.run(_trace(cfg))
    assert summ["faults_detected"] >= 1
    assert summ["fault_retries_succeeded"] == summ["faults_detected"]
    for rid in ref:
        assert np.array_equal(ref[rid], outs[rid]), rid


def test_restore_rejects_spec_mismatch(greedy_runs, setup, tmp_path):
    """A journal written in speculative mode cannot be restored into a
    non-speculative engine (or a different gamma): the tagged substreams
    and gamma-dependent bonus indices would change sampled streams."""
    cfg, params, mesh = setup
    d = str(tmp_path)
    sv = _sv(speculative=True, spec_gamma=2, checkpoint_every_ticks=6)
    jr = journal_lib.Journal(os.path.join(d, journal_lib.JOURNAL_NAME))
    inj = faults.FaultInjector(crash_window=(9, 9))
    eng = ContinuousServingEngine(cfg, params, mesh, serving=sv,
                                  fault_injector=inj, journal=jr)
    with pytest.raises(faults.EngineCrash):
        eng.run(_trace(cfg))
    for bad in (dataclasses.replace(sv, speculative=False),
                dataclasses.replace(sv, spec_gamma=3)):
        with pytest.raises(ValueError, match="speculative"):
            ContinuousServingEngine.restore(d, cfg, params, mesh,
                                            serving=bad)


def test_config_validation(setup):
    cfg, params, mesh = setup
    with pytest.raises(ValueError, match="spec_gamma"):
        _sv(speculative=True, spec_gamma=0)
    with pytest.raises(ValueError, match="mutually"):
        _sv(speculative=True, prefix_cache_bytes=1 << 20)
    lin = configs.get_smoke_config("slayformer-124m", attn_kind="slay")
    assert not api.supports_speculative(lin)
    with pytest.raises(ValueError, match="speculative"):
        ContinuousServingEngine(
            lin, api.init_params(lin, jax.random.PRNGKey(0)), mesh,
            serving=_sv(speculative=True))
