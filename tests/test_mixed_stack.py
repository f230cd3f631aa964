"""The mixed Mamba-2 / attention stack (``ArchConfig.layer_types``) against
the benchmark's plain reference, ``bench/reference/granite_hybrid.py``.

Every test cuts granite-4.0-h-micro's configuration file down (widths cut,
the pattern [M, M, A, M, A, M], vocabulary 256) and keeps its multipliers,
NoPE and norm eps; the weights are the benchmark's own
(``bench.weights.make``). Logits are compared in units of the reference
logits' standard deviation, the scale a wrong multiplier moves them on.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import serving, spec, weights
from repro.configs.base import ArchConfig, ServingConfig
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.serving import engine as engine_lib
from repro.serving import sampling
from repro.serving.engine import ContinuousServingEngine, Request

PATTERN = "mamba,mamba,attention,mamba,attention,mamba"
CUT = dict(num_layers=6, layer_types=PATTERN, d_model=64, num_heads=4,
           num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
           ssm_state=16, ssm_head_dim=16, chunk_size=8)
SEED = 2**33 + 7
# The program computes in bf16 and the reference in fp32 at `highest`: over
# six layers the program's logits lie at most 0.12 and on average (rms)
# 0.022 of the reference's standard deviation from it (two seeds). The
# limits leave twice that room; each multiplier left at its default
# (embeddings x1, residual x1, score scale 1/sqrt(dh), logits /1) moves
# the largest difference of the engine's logits to 0.53, 4.4, 1.9 and 28
# standard deviations. (The residual multiplier moves them least: pre-norm
# layers and the final norm see only its ratio to the small embedding.)
MAX_TOL, RMS_TOL = 0.25, 0.05
WRONG = [("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
         ("attention_multiplier", 0.0), ("logits_scaling", 1.0)]


@pytest.fixture(scope="module")
def granite():
    bench = spec.load_benchmark()
    with open(spec.config_path("granite-4.0-h-micro", bench)) as f:
        cfg = json.load(f)
    cfg = dict(cfg, arch=dict(cfg["arch"], **CUT))
    model = spec.model(cfg)
    params = weights.make(cfg["arch"], SEED, model)
    ref = jax.jit(lambda p, t: model.logits(p, cfg, t))
    return cfg, serving.arch_config(cfg), params, ref


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, 256, size=n).astype(
        np.int32)


def _distance(got, want) -> tuple[float, float]:
    """Largest and rms difference over the reference's std."""
    d = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    sd = float(np.std(want))
    return float(d.max()) / sd, float(np.sqrt(np.mean(d * d))) / sd


def test_layer_types_and_mixed_keys():
    cfg = ArchConfig(name="t", family="decoder", num_layers=3, d_model=8,
                     vocab_size=8, attn_kind="softmax",
                     layer_types="mamba,attention,mamba")
    assert cfg.layer_types == ("mamba", "attention", "mamba")
    assert hash(cfg) == hash(dataclasses.replace(cfg))
    with pytest.raises(ValueError):       # the mixed keys need a mixed stack
        ArchConfig(name="t", family="decoder", num_layers=1, d_model=8,
                   vocab_size=8, residual_multiplier=0.5)
    with pytest.raises(ValueError):       # a kind for every layer
        ArchConfig(name="t", family="decoder", num_layers=2, d_model=8,
                   vocab_size=8, attn_kind="softmax", layer_types="mamba")
    cut = ArchConfig(name="t", family="decoder", num_layers=2, d_model=8,
                     vocab_size=8, attn_kind="softmax",
                     layer_types="mamba,attention,mamba")
    assert cut.layer_types == ("mamba", "attention")  # cut in depth


def test_weights_tree_is_the_programs(granite):
    cfg, arch, params, _ = granite
    ours = jax.eval_shape(lambda: params)
    prog = api.abstract_params(arch)
    assert jax.tree.structure(ours) == jax.tree.structure(prog)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(prog)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    # Stacked by kind: 4 Mamba mixers and 2 attention blocks, not 6 of each.
    assert params["ssd"]["in_proj"].shape[0] == 4
    assert params["attn"]["wq"].shape[0] == 2
    # Mamba-2's own init: A in [1, 16], dt in [1e-3, 1e-1], D = 1.
    a = np.exp(np.asarray(params["ssd"]["a_log"], np.float32))
    dt = np.log1p(np.exp(np.asarray(params["ssd"]["dt_bias"], np.float32)))
    assert a.min() >= 0.99 and a.max() <= 16.1
    assert dt.min() >= 0.99e-3 and dt.max() <= 1.01e-1
    assert np.all(np.asarray(params["ssd"]["d_skip"], np.float32) == 1)


def test_forward_matches_reference(granite):
    _, arch, params, ref = granite
    toks = _tokens(40)
    want = ref(params, toks)
    got, _ = jax.jit(lambda p, t: api.forward(p, arch, {"tokens": t}))(
        params, toks[None])
    mx, rms = _distance(got[0], want)
    assert mx < MAX_TOL and rms < RMS_TOL, (mx, rms)


@pytest.mark.parametrize("schedule", [(13, 17, 7), (1, 8, 28),
                                      (5, 5, 5, 5, 5, 5, 5, 2)],
                         ids=["13-17-7", "1-8-28", "5x7-2"])
def test_chunked_prefill_matches_whole_prompt(granite, schedule):
    """Any chunk schedule ends in the whole prompt's state: the SSD scan,
    conv tails and rings continue exactly in real arithmetic. In bf16 a
    chunk of one token sums the scan in another order, which can round an
    activation one bf16 step (0.4%) the other way: every state leaf stays
    within 1% of its scale (0.39% read) and the last logits within 5% of
    their spread (1.2% read)."""
    _, arch, params, _ = granite
    toks = _tokens(sum(schedule), seed=3)
    want_lg, want = api.prefill(params, arch, {"tokens": toks[None]},
                                max_len=64)
    cache, lo = api.init_cache(arch, 1, 64), 0
    for n in schedule:
        lg, cache = api.prefill_chunk(arch, params, cache,
                                      toks[None, lo:lo + n])
        lo += n
    assert _distance(lg, want_lg)[0] < 0.05
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= 0.01 * np.abs(b).max()


def _serve(arch, params, prompts, *, max_new=12):
    """Serve ``prompts`` through the engine (3 slots, staggered arrivals,
    chunks of 8, K = 2) and record the logits every served token was
    sampled from, keyed (request, token index)."""
    seen = {}

    def record(logits, rids, idxs):
        for row, r, i in zip(np.asarray(logits, np.float32),
                             np.asarray(rids), np.asarray(idxs)):
            seen.setdefault((int(r), int(i)), row)

    orig = sampling.sample_tokens

    def sample_tokens(logits, rids, idxs, **kw):
        jax.debug.callback(record, logits, rids, idxs)
        return orig(logits, rids, idxs, **kw)

    mp = pytest.MonkeyPatch()
    mp.setattr(engine_lib.sampling, "sample_tokens", sample_tokens)
    try:
        eng = ContinuousServingEngine(
            arch, params, make_host_mesh(),
            serving=ServingConfig(num_slots=3, max_len=64, prefill_chunk=8,
                                  macro_ticks=2))
        rids = [eng.submit(Request(p, max_new_tokens=max_new, eos_id=-1,
                                   arrival_time=float(3 * i)))
                for i, p in enumerate(prompts)]
        outs, _ = eng.run()
        jax.effects_barrier()
    finally:
        mp.undo()
    return [(np.asarray(outs[r]), [seen[(r, i)] for i in range(max_new)])
            for r in rids], eng


def _engine_distance(granite, arch):
    """Prefill then decode through the engine, against the reference's
    full forward over prompt + served tokens, on logits."""
    _, _, params, ref = granite
    prompts = [_tokens(n, seed=10 + n) for n in (13, 5, 21, 9)]
    served, eng = _serve(arch, params, prompts)
    worst = (0.0, 0.0)
    for p, (out, rows) in zip(prompts, served):
        want = np.asarray(ref(params, np.concatenate([p, out[:-1]])))
        d = _distance(np.stack(rows), want[len(p) - 1:])
        worst = (max(worst[0], d[0]), max(worst[1], d[1]))
    return worst, eng


def test_engine_decode_matches_reference(granite):
    """Four requests over a 3-slot masked pool, admitted as slots free up
    and prompts of 5-21 tokens split into chunks of 8, each served 12
    tokens by the K = 2 macro-step."""
    (mx, rms), eng = _engine_distance(granite, granite[1])
    assert mx < MAX_TOL and rms < RMS_TOL, (mx, rms)
    assert eng.metrics.decode_dispatches > 0


@pytest.mark.parametrize("key,value", WRONG, ids=[k for k, _ in WRONG])
def test_engine_with_a_wrong_multiplier_fails(granite, key, value):
    arch = dataclasses.replace(granite[1], **{key: value})
    (mx, _), _ = _engine_distance(granite, arch)
    assert mx > MAX_TOL, (key, mx)


def test_pool_bytes_by_kind_of_state(granite):
    """The engine's pool counter: 3 slots of 4 Mamba layers' fp32 state
    (8 heads x 16 x 16) and conv tails (3 rows of 128 + 2 x 16), and 2
    attention layers' bf16 K and V rings of 64 rows x 2 heads x 16."""
    _, arch, params, _ = granite
    eng = ContinuousServingEngine(arch, params, make_host_mesh(),
                                  serving=ServingConfig(num_slots=3,
                                                        max_len=64))
    assert eng.metrics.pool_bytes == {
        "ssm_state": 4 * 3 * 8 * 16 * 16 * 4,
        "conv_tails": 4 * 3 * 3 * 160 * 4,
        "kv_rings": 2 * 2 * 3 * 64 * 2 * 16 * 2}
    assert eng.metrics.summary()["pool_bytes"] == eng.metrics.pool_bytes


# The benchmark's reference logits of slayformer at commit 8607e8a
# (bench/tests/test_models.py saves and reads them): the homogeneous
# families' program, in bf16, stays within a tenth of the logits' spread
# of them (it reads 0.03 at two layers); the float8 control lies 0.58-0.81
# away, so a change to that path's arithmetic shows.
PINNED = os.path.join(spec.BENCH_DIR, "tests", "fixtures",
                      "reference_logits_8607e8a.npz")
SMALL = dict(num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
             head_dim=16, d_ff=64, vocab_size=64)


@pytest.mark.parametrize("config", ["slayformer-124m",
                                    "slayformer-124m-softmax"])
def test_homogeneous_families_keep_the_pinned_logits(config):
    with open(spec.config_path(config, spec.load_benchmark())) as f:
        cfg = json.load(f)
    cfg = dict(cfg, arch=dict(cfg["arch"], **SMALL))
    arch = serving.arch_config(cfg)
    params = weights.make(cfg["arch"], 2**33 + 1, spec.model(cfg))
    toks = (np.arange(24, dtype=np.int32) * 7 + 3) % 64
    got, _ = jax.jit(lambda p, t: api.forward(p, arch, {"tokens": t}))(
        params, toks[None])
    got = np.asarray(got[0].astype(jnp.float32))[[0, 5, 11, 23]]
    with np.load(PINNED) as saved:
        want = saved[f"{config}/float32"]
    assert _distance(got, want)[0] < 0.1
