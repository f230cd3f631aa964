"""Every Pallas kernel compiles for a TPU v5e at slayformer-124m widths.

Ahead-of-time compiles against a *described* ``v5e:2x2`` topology: the TPU
compiler is installed with jax, so it lowers each kernel for a chip that is
not attached and refuses what the chip would refuse (block shapes off the
(8, 128) tiling, in-kernel relayouts Mosaic lacks, VMEM overruns). Nothing
runs. Interpret mode (the parity tests) checks none of that.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import decode_step, feature_map, slay_fused, slay_scan

CFG = get_config("slayformer-124m")
SCFG = CFG.slay_config()
D = CFG.resolved_head_dim            # 64
DV = D
M = SCFG.feature_dim                 # R·P·D = 3·8·16 = 384
T = CFG.chunk_size                   # 256
P, R_PRF = SCFG.num_anchors, SCFG.num_prf
BK = 32 * CFG.num_kv_heads           # a 32-slot serving pool, one row per kv head
BH = 2 * CFG.num_heads               # batch 2 of heads for the sequence kernels
L = 2 * T                            # two chunks: the carry crosses a boundary
BF = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sum(fn):
    """Scalar loss over a kernel's output, for jax.grad."""
    return lambda *a: jnp.sum(fn(*a).astype(jnp.float32))


def _cases():
    def fused(q, k, v, a, w):
        return slay_fused.fused_causal_attention(q, k, v, a, w, SCFG,
                                                 chunk_size=T)

    def scan(q, k, v):
        return slay_scan.causal_linear_attention(q, k, v, chunk_size=T)

    def fmap(u, a, w):
        return feature_map.slay_feature_map(u, a, w, SCFG, block_tokens=T)

    raw = [((BH, L, D), BF), ((BH, L, D), BF), ((BH, L, DV), BF),
           ((P, D), jnp.float32), ((R_PRF, D), jnp.float32)]
    feats = [((BH, L, M), BF), ((BH, L, M), BF), ((BH, L, DV), BF)]
    tokens = [((4 * T, D), BF), ((P, D), jnp.float32),
              ((R_PRF, D), jnp.float32)]
    state = [((BK, M), BF), ((BK, M), BF), ((BK, DV), BF),
             ((BK, M, DV), jnp.float32), ((BK, M), jnp.float32)]
    return {
        "decode": (decode_step.decode_linear_attention, state),
        "decode_masked": (decode_step.decode_linear_attention,
                          state + [((BK,), jnp.int32)]),
        "fused_fwd": (fused, raw),
        "fused_grad": (jax.grad(_sum(fused), argnums=(0, 1, 2, 3, 4)), raw),
        "scan_fwd": (scan, feats),
        "scan_grad": (jax.grad(_sum(scan), argnums=(0, 1, 2)), feats),
        "feature_map_fwd": (fmap, tokens),
        "feature_map_grad": (jax.grad(_sum(fmap), argnums=(0, 1, 2)),
                             tokens),
    }


@pytest.mark.parametrize("kernel", ["decode", "decode_masked", "fused_fwd",
                                    "fused_grad", "scan_fwd", "scan_grad",
                                    "feature_map_fwd", "feature_map_grad"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, shapes = _cases()[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
