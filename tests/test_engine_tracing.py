"""The serving engine's own instrumentation: host spans on the profiler's
clock, stable program names, and the admission counters.

A tiny engine runs under ``jax.profiler.trace`` and its ``.xplane.pb`` is
read back with ``ProfileData``: every ``engine.*`` span is there, the
decode spans nest inside their step, the prefill spans carry their
request's rid, and a macro-step writes the same spans whatever the number
of slots (nothing is emitted per token). Each jitted engine program lowers
to a module named after it.
"""
import collections
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ServingConfig
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.serving import journal as journal_lib
from repro.serving.engine import ContinuousServingEngine, Request

pytestmark = pytest.mark.serving

SPANS = {"engine.step", "engine.admit", "engine.prefill.chunk",
         "engine.prefill.first_token", "engine.prefill.install",
         "engine.decode.launch", "engine.decode.wait",
         "engine.decode.replay", "engine.journal.flush", "engine.checkpoint"}
DECODE = ("engine.decode.launch", "engine.decode.wait",
          "engine.decode.replay")


def _setup(attn_kind, **over):
    cfg = configs.get_smoke_config("slayformer-124m", attn_kind=attn_kind,
                                   num_layers=2, **over)
    return cfg, api.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=["slay", "softmax"])
def regime(request):
    """Both cache regimes: the constant-size linear state and the KV
    ring."""
    return _setup(request.param)


def _requests(cfg, n, plen=10, max_new=12):
    rng = np.random.default_rng(3)
    return [Request(rng.integers(3, cfg.vocab_size, size=plen + i)
                    .astype(np.int32), max_new_tokens=max_new, eos_id=-1,
                    arrival_time=float(i))
            for i in range(n)]


def _spans(log_dir):
    """[(name, start_ns, end_ns, stats)] of the trace's engine spans."""
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def _traced_run(cfg, params, sv, reqs, tmp_path, journal=False):
    jr = (journal_lib.Journal(os.path.join(tmp_path,
                                           journal_lib.JOURNAL_NAME))
          if journal else None)
    eng = ContinuousServingEngine(cfg, params, make_host_mesh(),
                                  serving=sv, journal=jr)
    eng.run(reqs[:1])                  # compile outside the trace
    log_dir = os.path.join(tmp_path, "trace")
    with jax.profiler.trace(log_dir):
        outs, _ = eng.run(reqs)
    if jr is not None:
        jr.close()
    return eng, outs, _spans(log_dir)


def _inside(spans, outer):
    _, s0, e0, _ = outer
    return [sp for sp in spans if sp is not outer and s0 <= sp[1]
            and sp[2] <= e0]


def test_every_span_appears_nested_and_keyed(regime, tmp_path):
    cfg, params = regime
    sv = ServingConfig(num_slots=2, max_len=64, prefill_chunk=4,
                       macro_ticks=4, temperature=0.0,
                       checkpoint_every_ticks=6)
    reqs = _requests(cfg, 4)
    eng, outs, spans = _traced_run(cfg, params, sv, reqs, tmp_path,
                                   journal=True)
    assert {s[0] for s in spans} == SPANS
    steps = [s for s in spans if s[0] == "engine.step"]
    assert {s[3]["kind"] for s in steps} <= {"prefill", "decode", "idle"}
    # Launch, wait and replay each sit inside a decode step, in order.
    for sp in spans:
        if sp[0] in DECODE:
            outer = [st for st in steps if st[1] <= sp[1] and sp[2] <= st[2]]
            assert len(outer) == 1 and outer[0][3]["kind"] == "decode"
    for st in steps:
        if st[3]["kind"] == "decode":
            names = [s[0] for s in _inside(spans, st) if s[0] in DECODE]
            assert names == list(DECODE)
    # The prefill spans of one request share its rid: admission, chunks
    # at successive offsets, the first token and the install.
    traced = set(range(1, 1 + len(reqs)))       # rid 0 ran before tracing
    by_rid = collections.defaultdict(list)
    for name, _, _, stats in spans:
        if name.startswith(("engine.admit", "engine.prefill.")):
            by_rid[stats["rid"]].append((name, stats))
    assert set(by_rid) == traced
    for rid, evs in by_rid.items():
        names = [n for n, _ in evs]
        assert names[0] == "engine.admit"
        assert names[-2:] == ["engine.prefill.first_token",
                              "engine.prefill.install"]
        offsets = [st["offset"] for n, st in evs
                   if n == "engine.prefill.chunk"]
        assert offsets == list(range(0, len(reqs[rid - 1].prompt), 4))
        slot = evs[0][1]["slot"]
        assert evs[-1][1]["slot"] == slot
        assert eng.metrics.per_request[rid].slot == slot
    assert all(len(outs[r]) == 12 for r in traced)


@pytest.mark.parametrize("attn_kind", ["slay", "softmax"])
def test_spans_per_macro_step_do_not_grow_with_slots(attn_kind, tmp_path):
    """Every decode step writes launch, wait and replay once, whether it
    replays 2 slots' tokens or 4: no span is emitted per token."""
    cfg, params = _setup(attn_kind)
    per_step = {}
    for slots in (2, 4):
        sv = ServingConfig(num_slots=slots, max_len=64, prefill_chunk=16,
                           macro_ticks=4, temperature=0.0)
        _, _, spans = _traced_run(cfg, params, sv, _requests(cfg, 2 * slots),
                                  os.path.join(tmp_path, str(slots)))
        counts = {tuple(sorted(collections.Counter(
                      s[0] for s in _inside(spans, st)).items()))
                  for st in spans if st[0] == "engine.step"
                  and st[3]["kind"] == "decode"}
        per_step[slots] = counts
    want = {tuple(sorted((n, 1) for n in DECODE))}
    assert per_step[2] == per_step[4] == want


def _lowered_module(fn, *args) -> str:
    text = fn.lower(*args).as_text()
    line = next(ln for ln in text.splitlines() if ln.startswith("module @"))
    return line.split()[1].lstrip("@")


def _program_modules(eng) -> dict:
    """Lowered module name of each jitted engine program, by attribute."""
    cfg, sv = eng.cfg, eng.serving
    p_abs, c_abs = eng._abstract
    S, L, C = sv.num_slots, sv.max_len, sv.prefill_chunk
    i32 = jax.ShapeDtypeStruct((S,), jnp.int32)
    b1 = jax.ShapeDtypeStruct((S,), jnp.bool_)
    one = jax.ShapeDtypeStruct((1,), jnp.int32)
    slot = jax.ShapeDtypeStruct((), jnp.int32)
    toks = jax.ShapeDtypeStruct((1, C), jnp.int32)
    batch = {"tokens": toks}
    src = api.abstract_cache(cfg, 1, L)
    args = {
        "_sample_fn": (jax.ShapeDtypeStruct((1, cfg.vocab_size),
                                            jnp.float32), one, one),
        "_write_fn": (c_abs, src, slot),
        "_reset_fn": (c_abs, slot),
        "_corrupt_fn": (c_abs, slot),
        "_chunk_fn": (p_abs, src, toks),
        "_chunk_embeds_fn": (p_abs, src, jax.ShapeDtypeStruct(
            (1, C, cfg.d_model), cfg.activation_dtype)),
        "_prefill_fn": (p_abs, batch),
        "_prefill_masked_fn": (p_abs, batch, one),
    }
    if eng._spec:
        d_abs = eng._draft_abstract
        dsrc = api.abstract_cache(eng.draft_cfg, 1, L)
        args.update({
            "_spec_fn": (p_abs, d_abs, c_abs) + (i32, b1) + (i32,) * 4,
            "_dwrite_fn": (d_abs, dsrc, slot),
            "_dreset_fn": (d_abs, slot),
            "_dchunk_fn": (p_abs, dsrc, toks),
            "_dprefill_fn": (p_abs, batch),
            "_dprefill_masked_fn": (p_abs, batch, one),
        })
    else:
        args["_macro_fn"] = (p_abs, c_abs, i32, b1) + (i32,) * 4
    with eng.mesh:
        return {k: _lowered_module(getattr(eng, k), *a)
                for k, a in args.items()}


def test_every_engine_program_has_its_own_name():
    mesh = make_host_mesh()
    cfg, params = _setup("slay")
    eng = ContinuousServingEngine(cfg, params, mesh, serving=ServingConfig(
        num_slots=2, max_len=64, prefill_chunk=8, macro_ticks=2))
    plain = _program_modules(eng)
    assert plain == {
        "_macro_fn": "jit_engine_macro_decode",
        "_sample_fn": "jit_engine_sample_first",
        "_write_fn": "jit_engine_write_slot",
        "_reset_fn": "jit_engine_reset_slot",
        "_corrupt_fn": "jit_engine_corrupt_slot",
        "_chunk_fn": "jit_engine_prefill_chunk",
        "_chunk_embeds_fn": "jit_engine_prefill_chunk_embeds",
        "_prefill_fn": "jit_engine_prefill",
        "_prefill_masked_fn": "jit_engine_prefill_masked"}
    vcfg, vparams = _setup("yat_spherical", slay_anchors=16, slay_prf=32)
    spec = ContinuousServingEngine(
        vcfg, vparams, mesh, serving=ServingConfig(
            num_slots=2, max_len=64, prefill_chunk=8, macro_ticks=2,
            speculative=True, spec_gamma=2))
    names = _program_modules(spec)
    assert names["_spec_fn"] == "jit_engine_spec_macro"
    assert {names[k] for k in ("_dwrite_fn", "_dreset_fn", "_dchunk_fn",
                               "_dprefill_fn", "_dprefill_masked_fn")} == {
        "jit_engine_draft_write_slot", "jit_engine_draft_reset_slot",
        "jit_engine_draft_prefill_chunk", "jit_engine_draft_prefill",
        "jit_engine_draft_prefill_masked"}
    assert len(set(names.values())) == len(names)
    assert len(set(plain.values())) == len(plain)


def test_admitted_wall_lies_between_arrival_and_first_token():
    cfg, params = _setup("slay")
    eng = ContinuousServingEngine(cfg, params, make_host_mesh(),
                                  serving=ServingConfig(
                                      num_slots=2, max_len=64,
                                      prefill_chunk=4, macro_ticks=4,
                                      temperature=0.0))
    eng.run(_requests(cfg, 4))
    for st in eng.metrics.per_request.values():
        assert st.arrival_wall <= st.admitted_wall <= st.first_token_wall


def test_dispatches_while_ready_count_held_back_admissions():
    """One slot free and a request ready while the pool decodes: counted.
    Nothing ready, or no slot free: not counted."""
    cfg, params = _setup("slay")
    eng = ContinuousServingEngine(cfg, params, make_host_mesh(),
                                  serving=ServingConfig(
                                      num_slots=2, max_len=128,
                                      prefill_chunk=32, macro_ticks=4,
                                      temperature=0.0))
    m = eng.metrics

    def submit():
        eng.submit(Request(np.full(8, 5, np.int32), max_new_tokens=60,
                           eos_id=-1, arrival_time=float(eng.tick)))

    submit()
    eng.step()                      # prefill A; the pool was empty
    assert (m.prefill_ticks, m.decode_dispatches) == (1, 0)
    submit()
    eng.step()                      # decode: B ready, slot 1 free
    assert (m.decode_dispatches, m.decode_dispatches_while_ready) == (1, 1)
    eng.step()                      # prefill B
    eng.step()                      # decode: nothing ready
    assert (m.decode_dispatches, m.decode_dispatches_while_ready) == (2, 1)
    submit()
    eng.step()                      # decode: C ready, no slot free
    assert m.prefill_ticks == 2
    assert (m.decode_dispatches, m.decode_dispatches_while_ready) == (3, 1)
