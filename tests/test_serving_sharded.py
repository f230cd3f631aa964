"""Data-axis sharded serving slot pool (DESIGN.md §8).

Two layers of coverage:

* In-process unit tests of the sharding resolution (pool_slot_axes,
  serving_vector_sharding specs, serving_param_rules, ServingConfig
  validation) and the shard-aware Scheduler — these need no devices.
* Multi-device contract checks (byte-identical streams mesh=(1,) vs
  mesh=(data=4,) at K=1/K=8, shard-local eviction/reuse, divisibility
  fallback, zero-collective decode HLO) — these need a forced 8-device CPU,
  and jax pins its device count at first init, so each check runs
  ``tests/sharded_driver.py`` in a subprocess with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``. CI additionally
  invokes the driver directly under that flag.
"""
import os
import subprocess
import sys

import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ServingConfig
from repro.distributed import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.serving.engine import Scheduler

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DRIVER = os.path.join(_REPO, "tests", "sharded_driver.py")


def _run_driver(check: str):
    env = dict(os.environ)
    # Append (not overwrite) so the child shares the parent's XLA config —
    # anything numerics-affecting must hit both sides of the parity check.
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, _DRIVER, "--check", check],
        capture_output=True, text=True, timeout=1800, env=env, cwd=_REPO)
    assert proc.returncode == 0, (
        f"sharded_driver --check {check} failed\n"
        f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    assert f"sharded_driver OK: {check}" in proc.stdout, proc.stdout


# ---------------------------------------------------------------------------
# In-process: sharding resolution + config + scheduler
# ---------------------------------------------------------------------------


def test_slot_shards_config_validation():
    with pytest.raises(ValueError, match="slot_shards"):
        ServingConfig(slot_shards=-1)
    with pytest.raises(ValueError, match="divisible"):
        ServingConfig(num_slots=6, slot_shards=4)
    assert ServingConfig(num_slots=8, slot_shards=4).slot_shards == 4
    assert ServingConfig().slot_shards == 0          # auto


def test_pool_slot_axes_host_mesh():
    """Size-1 data axis: always a single shard, never a fallback entry."""
    mesh = make_host_mesh()
    log = []
    axes, n = shd.pool_slot_axes(mesh, shd.DEFAULT_RULES, 4, 0, log)
    assert (axes, n) == ((), 1) and log == []
    axes, n = shd.pool_slot_axes(mesh, shd.DEFAULT_RULES, 4, 1, log)
    assert (axes, n) == ((), 1)


def test_serving_vector_sharding_specs_host_mesh():
    """On a size-1 data axis the control vectors replicate — the vector
    shardings always move in lockstep with the (replicated) pool. Sharded
    specs (P('data') on the slot dim) are asserted on a real 4-device mesh
    by the driver's ``collectives`` check."""
    mesh = make_host_mesh()
    v = shd.serving_vector_sharding(mesh, num_slots=4)
    assert v.spec == P(None)
    buf = shd.serving_vector_sharding(mesh, num_slots=4, leading=1)
    assert buf.spec == P(None, None)
    rep = shd.serving_vector_sharding(mesh, num_slots=4, slot_shards=1)
    assert rep.spec == P(None)


def test_serving_cache_sharding_host_mesh():
    """Host mesh (size-1 data axis): pool leaves replicate, shapes-only
    derivation still holds (no exceptions, full leaf coverage)."""
    import jax
    import jax.numpy as jnp
    mesh = make_host_mesh()
    abstract = {
        "kv": jax.ShapeDtypeStruct((2, 4, 8, 2, 16), jnp.bfloat16),
        "state": jax.ShapeDtypeStruct((2, 4, 2, 24, 16), jnp.float32),
        "lpos": jax.ShapeDtypeStruct((2, 4), jnp.int32),
        "pos": jax.ShapeDtypeStruct((4,), jnp.int32),
        "scalar": jax.ShapeDtypeStruct((), jnp.int32),
    }
    sh = shd.serving_cache_sharding(mesh, shd.DEFAULT_RULES, abstract,
                                    num_slots=4)
    assert sh["kv"].spec == P()
    assert sh["pos"].spec in (P(), P(None))   # replicated either way
    assert sh["scalar"].spec == P()


def test_serving_param_rules_strip_slot_axes():
    """Serving params replicate over the slot (data) axes, keep TP."""
    rules = shd.serving_param_rules(shd.DEFAULT_RULES)
    assert rules.embed is None
    assert rules.batch == "pod"
    assert rules.heads == "model" and rules.vocab == "model"


def test_scheduler_shard_balanced_admission():
    """Admission picks a free slot from the least-loaded shard (static
    contiguous ownership); with one shard it degrades to lowest-free-slot."""
    sched = Scheduler(ServingConfig(num_slots=4, max_len=32), slot_shards=2)
    assert [sched.shard_of(s) for s in range(4)] == [0, 0, 1, 1]
    from repro.serving.engine import Request
    import numpy as np
    req = Request(np.zeros(2, np.int32))
    for rid in range(3):
        sched.submit(rid, req)
    sched.poll_arrivals(0.0)
    rid, _, slot = sched.next_admission()
    assert (rid, slot) == (0, 0)
    sched.active[slot] = object()         # occupy shard 0
    rid, _, slot = sched.next_admission()
    assert (rid, slot) == (1, 2)          # balances onto shard 1
    sched.active[slot] = object()
    rid, _, slot = sched.next_admission()
    assert (rid, slot) == (2, 1)          # both loaded: lowest slot id
    single = Scheduler(ServingConfig(num_slots=4, max_len=32))
    single.submit(9, req)
    single.poll_arrivals(0.0)
    assert single.next_admission()[2] == 0


# ---------------------------------------------------------------------------
# Multi-device (subprocess under forced 8-device CPU)
# ---------------------------------------------------------------------------


@pytest.mark.serving
def test_sharded_stream_parity():
    """mesh=(1,) and mesh=(data=4,) emit byte-identical token streams for a
    fixed mixed-length Poisson trace, at K=8 and K=1, greedy and sampled,
    both cache regimes."""
    _run_driver("parity")


@pytest.mark.serving
def test_sharded_eviction_and_reuse():
    """Shard-local eviction/reuse on a 1-slot-per-shard pool, balanced
    admission across all shards, streams matching the single-shard run."""
    _run_driver("evict_reuse")


@pytest.mark.serving
def test_sharded_divisibility_fallback():
    """num_slots not divisible by the data axis replicates the pool and
    records the drop like the rule-engine fallback; streams stay exact."""
    _run_driver("fallback")


@pytest.mark.serving
def test_sharded_decode_has_no_collectives():
    """The compiled decode macro-step on mesh=(data=4,) contains no
    cross-shard collectives (the §8 hot-loop contract), both regimes."""
    _run_driver("collectives")


@pytest.mark.serving
def test_sharded_decode_kernel_runs_per_shard():
    """With the Pallas decode kernel on, a slot-sharded pool runs it once
    per shard (shard_map): streams match the one-device pool and the
    decode step stays collective-free."""
    _run_driver("kernel")
