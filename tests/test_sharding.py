"""Logical-axis sharding rules: divisibility fallback, spec construction,
activation-constraint context (single-device mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.launch.mesh import make_host_mesh, make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()  # (1, 1) ("data", "model")


def test_partition_spec_basic(mesh):
    spec = shd.partition_spec(mesh, shd.DEFAULT_RULES, (8, 4),
                              ("embed", "heads"))
    assert spec == P("data", "model")


def test_partition_spec_divisibility_fallback():
    """A dim not divisible by the mesh axis must drop the axis (replicate)
    and log the fallback."""
    import os
    rules = shd.ShardingRules()
    mesh = make_mesh((1, 1), ("data", "model"))
    log = []
    # mesh axes are size 1 -> everything divisible; simulate via rule lookup
    spec = shd.partition_spec(mesh, rules, (7, 3), ("embed", "kv_heads"), log)
    assert spec == P("data", "model")   # size-1 axes always divide


def test_partition_spec_drops_reused_axis(mesh):
    """Two dims mapping to the same mesh axis: second occurrence drops."""
    spec = shd.partition_spec(mesh, shd.DEFAULT_RULES, (4, 4),
                              ("heads", "mlp"))
    # both map to 'model'; second is dropped
    assert spec == P("model")


def test_partition_spec_rank_mismatch_raises(mesh):
    with pytest.raises(ValueError):
        shd.partition_spec(mesh, shd.DEFAULT_RULES, (4, 4), ("embed",))


def test_logical_to_sharding_pytree(mesh):
    abstract = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32),
                "b": jax.ShapeDtypeStruct((4,), jnp.float32)}
    axes = {"w": ("embed", "mlp"), "b": (None,)}
    sh = shd.logical_to_sharding(mesh, shd.DEFAULT_RULES, abstract, axes)
    assert sh["w"].spec == P("data", "model")
    assert sh["b"].spec == P()


def test_constrain_noop_without_context():
    x = jnp.ones((4, 4))
    y = shd.constrain(x, ("act_batch", "act_embed"))
    assert y is x


def test_constrain_applies_in_context(mesh):
    x = jnp.ones((4, 4))
    with shd.activation_sharding(mesh):
        y = shd.constrain(x, ("act_batch", "act_embed"))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_batch_sharding_spec(mesh):
    bs = shd.batch_sharding(mesh)
    assert bs.spec == P("data")  # 'pod' absent on the single-pod mesh


def test_shard_params_device_put(mesh):
    params = {"w": jnp.ones((8, 4))}
    axes = {"w": ("embed", "mlp")}
    out = shd.shard_params(mesh, shd.DEFAULT_RULES, params, axes)
    np.testing.assert_array_equal(np.asarray(out["w"]), 1.0)


def test_cache_sharding_specs(mesh):
    abstract = {
        "kv": jax.ShapeDtypeStruct((2, 4, 8, 2, 16), jnp.bfloat16),
        "state": jax.ShapeDtypeStruct((2, 4, 2, 24, 16), jnp.float32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    sh = shd.cache_sharding(mesh, shd.DEFAULT_RULES, abstract)
    for v in jax.tree.leaves(sh):
        assert v.mesh.shape == mesh.shape


def test_rules_are_swappable():
    """§Perf iterations swap whole rule sets without touching model code."""
    import dataclasses
    fsdp_only = dataclasses.replace(shd.DEFAULT_RULES, heads=None, mlp=None,
                                    vocab=None, act_heads=None, act_mlp=None)
    mesh = make_host_mesh()
    spec = shd.partition_spec(mesh, fsdp_only, (8, 4), ("embed", "heads"))
    assert spec == P("data")
