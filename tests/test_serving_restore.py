"""Crash-safe serving (DESIGN.md §12): write-ahead journal framing with
tolerant torn-tail replay, atomic checkpoint save/load with corrupt-file
fallback, and the kill-and-restore byte-identity contract — crashes
mid-decode and mid-chunked-prefill, truncated/corrupt journal tails,
restore onto a different slot count, and exactly-once (re)delivery."""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ServingConfig
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.serving import checkpoint as ckpt_lib
from repro.serving import faults
from repro.serving import journal as journal_lib
from repro.serving.engine import ContinuousServingEngine, Request

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_smoke_config("slayformer-124m")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    return cfg, params, mesh


@pytest.fixture(scope="module")
def ring_setup():
    cfg = configs.get_smoke_config("slayformer-124m", attn_kind="softmax")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _sv(**kw):
    return ServingConfig(**{"num_slots": 2, "max_len": 64,
                            "prefill_chunk": 4, "macro_ticks": 4,
                            "temperature": 0.7,
                            "checkpoint_every_ticks": 6, **kw})


def _trace(cfg, n=3, max_new=12, plen=5):
    rng = np.random.default_rng(7)
    return [Request(rng.integers(3, cfg.vocab_size,
                                 size=plen).astype(np.int32),
                    max_new_tokens=max_new, arrival_time=float(i))
            for i in range(n)]


def _baseline(cfg, params, mesh, sv, **tr):
    """Fault-free reference streams for the trace (no journal)."""
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=dataclasses.replace(sv, checkpoint_every_ticks=0))
    return eng.run(_trace(cfg, **tr))


def _crash_run(cfg, params, mesh, d, sv, *, crash_window=(8, 14), **tr):
    """Run the trace against a journaled engine until the injected crash
    kills it mid-flight; returns the dead engine."""
    jr = journal_lib.Journal(os.path.join(d, journal_lib.JOURNAL_NAME))
    inj = faults.FaultInjector(seed=3, crash_window=crash_window)
    eng = ContinuousServingEngine(cfg, params, mesh, serving=sv,
                                  fault_injector=inj, journal=jr)
    with pytest.raises(faults.EngineCrash):
        eng.run(_trace(cfg, **tr))
    return eng


# -- journal unit behavior ---------------------------------------------------


def test_journal_roundtrip_and_replay(tmp_path):
    p = str(tmp_path / "j.wal")
    with journal_lib.Journal(p) as j:
        j.append({"t": "meta", "v": 1, "seed": 0})
        j.append({"t": "admit", "rid": 0, "prompt": [1, 2, 3]})
        j.append({"t": "tok", "rid": 0, "tok": 42, "idx": 0})
        j.append({"t": "fin", "rid": 0, "reason": "length", "tick": 3})
        j.flush()
        assert j.flushes == 1 and not j.dirty
    st = journal_lib.replay(p)
    assert st.meta["seed"] == 0
    assert st.admits[0]["prompt"] == [1, 2, 3]
    assert st.tokens[0] == [42]
    assert st.fins[0] == "length"
    assert not st.dropped_tail
    assert st.valid_bytes == os.path.getsize(p)


def test_journal_torn_tail_dropped_and_truncated(tmp_path):
    """A torn (partial) final record is dropped by replay and physically
    truncated when the journal reopens for append — a corrupt tail can
    never shadow records written after recovery."""
    p = str(tmp_path / "j.wal")
    with journal_lib.Journal(p) as j:
        j.append({"t": "admit", "rid": 0, "prompt": [1]})
        j.append({"t": "tok", "rid": 0, "tok": 5, "idx": 0})
        j.flush()
    whole = os.path.getsize(p)
    with open(p, "ab") as f:                   # torn write: half a record
        f.write(b'deadbeef {"t": "tok", "rid": 0,')
    st = journal_lib.replay(p)
    assert st.dropped_tail and st.valid_bytes == whole
    assert st.tokens[0] == [5]                 # intact prefix survives
    with journal_lib.Journal(p, truncate_to=st.valid_bytes) as j2:
        j2.append({"t": "tok", "rid": 0, "tok": 6, "idx": 1})
        j2.flush()
    assert journal_lib.replay(p).tokens[0] == [5, 6]


def test_journal_crc_corruption_drops_suffix(tmp_path):
    """A bit-flip in the middle of the file invalidates that record's CRC;
    replay keeps only the records before it (suffix ordering after a bad
    record is no longer trustworthy)."""
    p = str(tmp_path / "j.wal")
    with journal_lib.Journal(p) as j:
        for i in range(4):
            j.append({"t": "tok", "rid": 0, "tok": i, "idx": i})
        j.flush()
    with open(p, "rb") as f:
        lines = f.readlines()
    lines[2] = lines[2].replace(b'"tok"', b'"toX"')
    with open(p, "wb") as f:
        f.writelines(lines)
    st = journal_lib.replay(p)
    assert st.dropped_tail
    assert st.tokens[0] == [0, 1]              # records 2, 3 both dropped


def test_journal_retry_record_resets_stream(tmp_path):
    p = str(tmp_path / "j.wal")
    with journal_lib.Journal(p) as j:
        j.append({"t": "tok", "rid": 1, "tok": 9, "idx": 0})
        j.append({"t": "retry", "rid": 1})
        j.append({"t": "tok", "rid": 1, "tok": 4, "idx": 0})
        j.flush()
    st = journal_lib.replay(p)
    assert st.tokens[1] == [4] and st.retries[1] == 1


# -- checkpoint unit behavior ------------------------------------------------


def _flip_last_byte(path):
    """Invert the final payload byte — the sha256 check must catch it."""
    with open(path, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        b = f.read(1)[0]
        f.seek(-1, os.SEEK_END)
        f.write(bytes([b ^ 0xFF]))


def test_checkpoint_save_load_roundtrip(tmp_path):
    p = str(tmp_path / "ckpt-000000000007.ckpt")
    state = {"version": 1, "tick": 7,
             "arr": np.arange(12, dtype=np.float32).reshape(3, 4)}
    ckpt_lib.save(p, state)
    assert not any(f.endswith(".tmp") for f in os.listdir(str(tmp_path)))
    got = ckpt_lib.load(p)
    assert got["tick"] == 7
    np.testing.assert_array_equal(got["arr"], state["arr"])


def test_checkpoint_corruption_detected_and_skipped(tmp_path):
    d = str(tmp_path)
    ckpt_lib.save(ckpt_lib.checkpoint_path(d, 3), {"tick": 3})
    newest = ckpt_lib.checkpoint_path(d, 9)
    ckpt_lib.save(newest, {"tick": 9})
    _flip_last_byte(newest)
    with pytest.raises(ckpt_lib.CheckpointError):
        ckpt_lib.load(newest)
    # latest_valid falls back to the intact older checkpoint.
    assert ckpt_lib.latest_valid(d)["tick"] == 3
    assert [t for t, _ in ckpt_lib.list_checkpoints(d)] == [9, 3]


def test_checkpoint_latest_valid_empty_dir(tmp_path):
    assert ckpt_lib.latest_valid(str(tmp_path)) is None


# -- kill-and-restore byte-identity across regimes ---------------------------


@pytest.mark.chaos
@pytest.mark.parametrize("regime", ["constant_state", "kv_ring", "paged"])
def test_crash_restore_byte_identical(setup, ring_setup, regime, tmp_path):
    """Kill the engine mid-flight (seeded crash injector), restore from
    disk, finish — merged streams are byte-identical to a fault-free run,
    replay is observed and deduped, and nothing leaks."""
    cfg, params, mesh = setup
    kw = {}
    if regime != "constant_state":
        cfg, params = ring_setup
    if regime == "paged":
        kw["page_size"] = 16
    sv = _sv(**kw)
    base, _ = _baseline(cfg, params, mesh, sv)
    d = str(tmp_path)
    _crash_run(cfg, params, mesh, d, sv)
    got = {}
    eng2 = ContinuousServingEngine.restore(
        d, cfg, params, mesh, serving=sv,
        on_token=lambda rid, tok: got.setdefault(rid, []).append(tok))
    assert eng2.recovery["wall_s"] >= 0.0
    outs, s = eng2.run()
    assert set(outs) == set(base)
    for rid in base:
        np.testing.assert_array_equal(outs[rid], base[rid])
    assert s["tokens_replayed"] > 0
    assert s["journal_bytes"] > 0
    assert s["final_occupancy"] == 0 and s["final_queue_depth"] == 0
    assert s["final_pages_in_use"] == 0
    # Exactly-once streaming: post-restore callbacks got precisely the
    # journal-horizon suffix of each stream, never a replayed token.
    for rid, toks in got.items():
        np.testing.assert_array_equal(
            toks, np.asarray(outs[rid])[len(outs[rid]) - len(toks):])


@pytest.mark.chaos
def test_crash_uses_checkpoint_and_resumes_residents(setup, tmp_path):
    """With the crash landing after the first periodic checkpoint, restore
    actually consumes it: device state comes back via the snapshot and at
    least the pre-crash terminations are known without re-decoding."""
    cfg, params, mesh = setup
    sv = _sv()
    d = str(tmp_path)
    eng = _crash_run(cfg, params, mesh, d, sv)
    assert eng.metrics.checkpoints_written >= 1
    eng2 = ContinuousServingEngine.restore(d, cfg, params, mesh, serving=sv)
    rec = eng2.recovery
    assert rec["checkpoint_used"] and rec["checkpoint_tick"] >= 1
    assert rec["resident_resumed"] + rec["requeued"] >= 1
    outs, s = eng2.run()
    base, _ = _baseline(cfg, params, mesh, sv)
    for rid in base:
        np.testing.assert_array_equal(outs[rid], base[rid])
    assert s["checkpoints_written"] >= 0 and s["journal_bytes"] > 0


@pytest.mark.chaos
def test_crash_mid_chunked_prefill_restores_byte_identical(setup, tmp_path):
    """Crash while a long prompt is mid-chunked-prefill: the checkpoint
    deliberately excludes the half-prefilled slot, so the request
    re-admits from its journaled prompt and re-runs the identical chunk
    schedule — streams still byte-identical."""
    cfg, params, mesh = setup
    sv = _sv(checkpoint_every_ticks=2)
    tr = dict(n=2, max_new=6, plen=24)          # 24/4 = 6 prefill chunks
    base, _ = _baseline(cfg, params, mesh, sv, **tr)
    d = str(tmp_path)
    eng = _crash_run(cfg, params, mesh, d, sv, crash_window=(3, 3), **tr)
    assert eng.tick <= 6                        # died inside prefill
    eng2 = ContinuousServingEngine.restore(d, cfg, params, mesh, serving=sv)
    outs, s = eng2.run()
    assert set(outs) == set(base)
    for rid in base:
        np.testing.assert_array_equal(outs[rid], base[rid])
    assert s["final_occupancy"] == 0 and s["final_pages_in_use"] == 0


@pytest.mark.chaos
def test_restore_with_truncated_journal_tail(setup, tmp_path):
    """Chop bytes off the journal's final record post-crash (a torn write
    at kill time). The lost suffix tokens simply regenerate — recovery
    falls back as far as needed and streams stay byte-identical."""
    cfg, params, mesh = setup
    sv = _sv()
    base, _ = _baseline(cfg, params, mesh, sv)
    d = str(tmp_path)
    _crash_run(cfg, params, mesh, d, sv)
    jpath = os.path.join(d, journal_lib.JOURNAL_NAME)
    with open(jpath, "r+b") as f:
        f.truncate(os.path.getsize(jpath) - 5)
    assert journal_lib.replay(jpath).dropped_tail
    eng2 = ContinuousServingEngine.restore(d, cfg, params, mesh, serving=sv)
    assert eng2.recovery["journal_dropped_tail"]
    outs, s = eng2.run()
    assert set(outs) == set(base)
    for rid in base:
        np.testing.assert_array_equal(outs[rid], base[rid])
    assert s["final_occupancy"] == 0


@pytest.mark.chaos
def test_restore_onto_different_slot_count(setup, tmp_path):
    """A checkpoint from a 2-slot engine is rejected wholesale when
    restoring with num_slots=3 (geometry gate) — recovery degrades to
    journal-only replay and the streams are still byte-identical."""
    cfg, params, mesh = setup
    sv = _sv()
    base, _ = _baseline(cfg, params, mesh, sv)
    d = str(tmp_path)
    eng = _crash_run(cfg, params, mesh, d, sv)
    assert eng.metrics.checkpoints_written >= 1
    sv3 = _sv(num_slots=3)
    eng2 = ContinuousServingEngine.restore(d, cfg, params, mesh, serving=sv3)
    assert not eng2.recovery["checkpoint_used"]
    outs, s = eng2.run()
    assert set(outs) == set(base)
    for rid in base:
        np.testing.assert_array_equal(outs[rid], base[rid])
    assert s["tokens_replayed"] > 0
    assert s["final_occupancy"] == 0


@pytest.mark.chaos
def test_restore_redelivers_exactly_once(setup, tmp_path):
    """redeliver=True re-fires on_token/on_finish for the journaled
    prefix at restore time; with the post-restore stream appended, a
    consumer that lost its own state sees every token exactly once."""
    cfg, params, mesh = setup
    sv = _sv()
    base, _ = _baseline(cfg, params, mesh, sv)
    d = str(tmp_path)
    _crash_run(cfg, params, mesh, d, sv)
    got, fins = {}, []
    eng2 = ContinuousServingEngine.restore(
        d, cfg, params, mesh, serving=sv, redeliver=True,
        on_token=lambda rid, tok: got.setdefault(rid, []).append(tok),
        on_finish=lambda rid, why: fins.append((rid, why)))
    outs, _ = eng2.run()
    for rid in base:
        np.testing.assert_array_equal(got.get(rid, []), outs[rid])
    assert sorted(rid for rid, _ in fins) == sorted(base)
    assert len(fins) == len(set(rid for rid, _ in fins))   # once per rid


def test_restore_refuses_mismatched_sampling_config(setup, tmp_path):
    """Byte-identity is only promised under the exact sampling config the
    journal was written with — a different seed/temperature at restore is
    a hard error, not silent divergence."""
    cfg, params, mesh = setup
    d = str(tmp_path)
    _crash_run(cfg, params, mesh, d, _sv())
    with pytest.raises(ValueError, match="seed"):
        ContinuousServingEngine.restore(d, cfg, params, mesh,
                                        serving=_sv(seed=123))
    with pytest.raises(ValueError, match="temperature"):
        ContinuousServingEngine.restore(d, cfg, params, mesh,
                                        serving=_sv(temperature=0.9))


@pytest.mark.chaos
def test_restore_skips_corrupt_newest_checkpoint(setup, tmp_path):
    """Corrupting the newest checkpoint on disk exercises latest_valid's
    fallback inside the real recovery path: the older intact checkpoint
    (or journal-only replay) still yields byte-identical streams."""
    cfg, params, mesh = setup
    sv = _sv(checkpoint_every_ticks=2)
    base, _ = _baseline(cfg, params, mesh, sv)
    d = str(tmp_path)
    eng = _crash_run(cfg, params, mesh, d, sv)
    assert eng.metrics.checkpoints_written >= 2
    ticks = [t for t, _ in ckpt_lib.list_checkpoints(d)]
    _flip_last_byte(ckpt_lib.checkpoint_path(d, ticks[0]))
    eng2 = ContinuousServingEngine.restore(d, cfg, params, mesh, serving=sv)
    assert eng2.recovery["checkpoint_used"]
    assert eng2.recovery["checkpoint_tick"] == ticks[1]
    outs, _ = eng2.run()
    for rid in base:
        np.testing.assert_array_equal(outs[rid], base[rid])
