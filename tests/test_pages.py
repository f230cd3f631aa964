"""Paged slot memory (DESIGN.md §11): PagePool free-list invariants under
randomized admit/evict churn, shard-block confinement, the shard-explicit
device gather/scatter vs a dense numpy reference, whole-page install /
zero / NaN-attribution ops, and engine-level paged-vs-unpaged stream
byte-identity (including under chaos fault injection)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ServingConfig
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.serving import pages
from repro.serving.engine import ContinuousServingEngine, Request
from repro.serving.faults import FaultInjector
from sharded_driver import poisson_trace


# ---------------------------------------------------------------------------
# PagePool (host allocator)
# ---------------------------------------------------------------------------


def test_pool_alloc_free_roundtrip():
    pool = pages.PagePool(num_slots=4, num_pages=16, page_size=8,
                          pages_per_slot=4)
    assert pool.pages_for(1) == 1 and pool.pages_for(8) == 1
    assert pool.pages_for(9) == 2 and pool.pages_for(64) == 4  # capped
    got = pool.alloc(2, need_rows=17)            # ceil(17/8) = 3 pages
    assert len(got) == 3 and pool.pages_in_use() == 3
    assert pool.slot_pages(2) == got
    assert pool.pages_peak == 3
    pool.check()
    assert pool.free_slot(2) == 3
    assert pool.pages_in_use() == 0 and pool.slot_pages(2) == []
    assert pool.pages_peak == 3                  # high-water survives free
    pool.check()


def test_pool_alloc_errors():
    pool = pages.PagePool(num_slots=2, num_pages=4, page_size=8,
                          pages_per_slot=4)
    pool.alloc(0, need_rows=24)                  # 3 of 4 pages
    with pytest.raises(RuntimeError, match="already holds pages"):
        pool.alloc(0, need_rows=8)
    assert not pool.can_alloc(1, need_rows=16)   # 2 needed, 1 free
    with pytest.raises(RuntimeError, match="free pages"):
        pool.alloc(1, need_rows=16)
    assert pool.can_alloc(1, need_rows=8)
    pool.alloc(1, need_rows=8)
    pool.check()


def test_pool_geometry_validation():
    with pytest.raises(ValueError, match="page_size"):
        pages.PagePool(2, 4, 0, 2)
    with pytest.raises(ValueError, match="num_pages"):
        pages.PagePool(4, 6, 8, 2, shards=4)
    with pytest.raises(ValueError, match="num_slots"):
        pages.PagePool(6, 8, 8, 2, shards=4)


def test_pool_shard_block_confinement():
    """A slot only ever receives pages from its own shard's contiguous
    block — the invariant the collective-free device indexing relies on."""
    pool = pages.PagePool(num_slots=4, num_pages=8, page_size=4,
                          pages_per_slot=2, shards=2)
    for slot in range(4):
        got = pool.alloc(slot, need_rows=8)
        d = pool.shard_of(slot)
        lo, hi = d * 4, (d + 1) * 4
        assert all(lo <= p < hi for p in got), (slot, got)
    pool.check()
    # Shard 0 exhausted: its slots can't borrow from shard 1's free block.
    pool.free_slot(2)
    assert pool.free_in_shard(1) == 2 and pool.free_in_shard(0) == 0
    assert not pool.can_alloc(1, need_rows=4)    # slot 1 lives in shard 0
    assert pool.can_alloc(2, need_rows=4)        # shard 1 has room
    pool.check()


@pytest.mark.parametrize("shards", [1, 2])
def test_pool_churn_property(rng, shards):
    """Randomized admit/evict churn: the allocator never double-assigns,
    never leaks, and its mirrors stay consistent (check() audits after
    every op); draining every slot returns the pool to all-free."""
    pool = pages.PagePool(num_slots=8, num_pages=32, page_size=4,
                          pages_per_slot=4, shards=shards)
    held: set[int] = set()
    peak = 0
    for _ in range(300):
        slot = int(rng.integers(8))
        if slot in held:
            pool.free_slot(slot)
            held.discard(slot)
        else:
            need = int(rng.integers(1, 17))
            if pool.can_alloc(slot, need):
                got = pool.alloc(slot, need)
                assert len(got) == pool.pages_for(need)
                held.add(slot)
        peak = max(peak, pool.pages_in_use())
        assert pool.pages_in_use() == sum(
            len(pool.slot_pages(s)) for s in held)
        pool.check()
    for slot in sorted(held):
        pool.free_slot(slot)
    assert pool.pages_in_use() == 0
    assert pool.pages_peak == peak
    pool.check()


# ---------------------------------------------------------------------------
# Device ops vs dense reference
# ---------------------------------------------------------------------------


def _dense_ref(leaf, table):
    """Numpy oracle for gather_ring: table walk, unmapped pages zero."""
    P, page = leaf.shape[:2]
    S, Lp = table.shape
    out = np.zeros((S, Lp * page) + leaf.shape[2:], leaf.dtype)
    for s in range(S):
        for j in range(Lp):
            p = int(table[s, j])
            if p >= 0:
                out[s, j * page:(j + 1) * page] = leaf[p]
    return out


def _churned_pool(rng, shards):
    pool = pages.PagePool(num_slots=4, num_pages=8, page_size=4,
                          pages_per_slot=2, shards=shards)
    for slot in (0, 1, 3):                       # slot 2 left unmapped
        pool.alloc(slot, need_rows=int(rng.integers(1, 9)))
    pool.free_slot(1)                            # churn: a freed slot too
    pool.check()
    return pool


@pytest.mark.parametrize("shards", [1, 2])
def test_gather_matches_dense_reference(rng, shards):
    pool = _churned_pool(rng, shards)
    leaf = rng.standard_normal((8, 4, 3)).astype(np.float32)
    state = pool.device_vectors()
    got = np.asarray(pages.gather_ring(jnp.asarray(leaf), state))
    np.testing.assert_array_equal(got, _dense_ref(leaf, pool.table))


@pytest.mark.parametrize("shards", [1, 2])
def test_scatter_gather_roundtrip(rng, shards):
    """scatter then gather reproduces the dense rows of owned pages; rows
    of unmapped logical pages read zero; free pages keep their old bytes
    (the gather mask, not the scatter, is what hides them)."""
    pool = _churned_pool(rng, shards)
    state = pool.device_vectors()
    leaf0 = rng.standard_normal((8, 4, 3)).astype(np.float32)
    dense = rng.standard_normal((4, 8, 3)).astype(np.float32)
    leaf1 = pages.scatter_ring(jnp.asarray(leaf0), jnp.asarray(dense),
                               state)
    back = np.asarray(pages.gather_ring(leaf1, state))
    want = dense.copy()
    for s in range(4):
        for j in range(2):
            if pool.table[s, j] < 0:
                want[s, j * 4:(j + 1) * 4] = 0.0
    np.testing.assert_array_equal(back, want)
    leaf1 = np.asarray(leaf1)
    for p in range(8):
        if pool.owner_slot[p] < 0:               # free page: untouched
            np.testing.assert_array_equal(leaf1[p], leaf0[p])


def test_write_slot_pages_overwrites_owner_only(rng):
    pool = _churned_pool(rng, 1)
    state = pool.device_vectors()
    leaf = jnp.asarray(rng.standard_normal((2, 8, 4, 3)).astype(np.float32))
    src = rng.standard_normal((2, 1, 8, 3)).astype(np.float32)
    out = np.asarray(pages.write_slot_pages(leaf, jnp.asarray(src),
                                            jnp.int32(0), state))
    for p in range(8):
        if pool.owner_slot[p] == 0:
            j = int(pool.owner_lp[p])
            np.testing.assert_array_equal(
                out[:, p], src[:, 0, j * 4:(j + 1) * 4])
        else:                                    # other owners + free pages
            np.testing.assert_array_equal(out[:, p],
                                          np.asarray(leaf)[:, p])


def test_pages_finite_attributes_nan_to_owner_only(rng):
    """A NaN page counts against its owning slot alone; a stale NaN in a
    *freed* page (quarantined owner) counts against nobody; zeroing the
    owned pages clears the flag."""
    pool = _churned_pool(rng, 1)                 # slots 0,3 own; 1,2 don't
    state = pool.device_vectors()
    leaf = jnp.zeros((2, 8, 4, 3), jnp.float32)  # (layers, P, page, tail)
    bad = pages.corrupt_slot_pages(leaf, jnp.int32(3), state)
    ok = np.asarray(pages.pages_finite([bad], state, num_slots=4))
    assert ok.tolist() == [True, True, True, False]
    # Free slot 3 host-side: the NaN bytes persist in the (now free) pages
    # but no live slot is blamed for them.
    pool.free_slot(3)
    st2 = pool.device_vectors()
    ok2 = np.asarray(pages.pages_finite([bad], st2, num_slots=4))
    assert ok2.tolist() == [True, True, True, True]
    # The §11 reset contract: zeroing via the OLD mapping scrubs the NaNs
    # before the pages can be re-issued.
    clean = pages.write_zero_pages(bad, jnp.int32(3), state)
    assert bool(jnp.all(jnp.isfinite(clean)))


# ---------------------------------------------------------------------------
# Engine level: byte identity + leak-freedom under churn
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def paged_setup():
    cfg = configs.get_smoke_config("slayformer-124m", attn_kind="softmax")
    assert api.supports_paging(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, make_host_mesh()


def _trace(cfg, n, seed, max_new=8):
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(3, cfg.vocab_size,
                                 size=int(rng.integers(3, 12)))
                    .astype(np.int32),
                    max_new_tokens=max_new, arrival_time=float(i))
            for i in range(n)]


def _run(cfg, params, mesh, reqs, *, page_size=0, injector=None, **kw):
    eng = ContinuousServingEngine(
        cfg, params, mesh, fault_injector=injector,
        serving=ServingConfig(**{"num_slots": 2, "max_len": 64,
                                 "prefill_chunk": 4, "macro_ticks": 4,
                                 "page_size": page_size, **kw}))
    outs, summary = eng.run([Request(r.prompt, max_new_tokens=r.max_new_tokens,
                                     arrival_time=r.arrival_time)
                             for r in reqs])
    return eng, outs, summary


@pytest.mark.serving
@pytest.mark.parametrize("trace", ["mixed", "poisson"])
def test_engine_paged_streams_byte_identical(paged_setup, trace):
    """Paged KV ring == unpaged: token streams byte-identical, page math
    visible in the summary, zero pages leaked after drain — on a mixed
    trace at K=4 and on four Poisson requests (16 new tokens each) at
    K=8 in 32-row rings."""
    cfg, params, mesh = paged_setup
    if trace == "mixed":
        reqs, kw = _trace(cfg, 5, seed=3), {}
    else:
        reqs = poisson_trace(np.random.default_rng(1234), 4, 1.0, (3, 8),
                             cfg.vocab_size, 16)
        kw = {"max_len": 32, "macro_ticks": 8}
    _, o1, s1 = _run(cfg, params, mesh, reqs, **kw)
    e2, o2, s2 = _run(cfg, params, mesh, reqs, page_size=8, **kw)
    assert s2["requests_completed"] == len(reqs) == s1["requests_completed"]
    for rid in o1:
        np.testing.assert_array_equal(o1[rid], o2[rid])
    assert s1.get("num_pages", 0) == 0           # unpaged run: no pool
    assert s2["num_pages"] == 2 * (kw.get("max_len", 64) // 8)
    assert s2["pages_peak"] >= 1
    assert s2["final_pages_in_use"] == 0
    e2.page_pool.check()


@pytest.mark.serving
def test_engine_short_requests_reserve_fewer_pages(paged_setup):
    """The memory-sharing win: a short request pins ceil(need/page) pages,
    not the whole slot ring."""
    cfg, params, mesh = paged_setup
    reqs = [Request(np.int32([5, 6, 7]), max_new_tokens=4,
                    arrival_time=0.0)]
    e, _, s = _run(cfg, params, mesh, reqs, page_size=8)
    assert s["pages_peak"] == 1                  # 3 + 4 rows -> 1 of 8 pages
    assert s["final_pages_in_use"] == 0
    e.page_pool.check()


@pytest.mark.serving
@pytest.mark.chaos
def test_engine_paged_no_leaks_under_chaos(paged_setup):
    """Fault-injection churn (NaN quarantine + cancels) over the paged
    pool: every exit path returns its pages, the allocator audit passes,
    and retried streams still match the fault-free paged run."""
    cfg, params, mesh = paged_setup
    reqs = _trace(cfg, 6, seed=5, max_new=6)
    _, clean, _ = _run(cfg, params, mesh, reqs, page_size=8)
    inj = FaultInjector(seed=2, nan_every=5, cancel_every=9)
    e, outs, s = _run(cfg, params, mesh, reqs, page_size=8, injector=inj,
                      fault_retries=3)
    assert s["requests_terminated"] == len(reqs)
    assert s["faults_detected"] >= 1             # the injector actually fired
    assert s["final_pages_in_use"] == 0
    assert s["final_occupancy"] == 0
    e.page_pool.check()
    for rid, toks in outs.items():
        reason = e.metrics.per_request[rid].finish_reason
        if reason in ("eos", "length"):          # survivors: exact replay
            np.testing.assert_array_equal(toks, clean[rid])
