"""Pallas TPU kernels vs pure-jnp oracles, interpret=True on CPU.

Per the assignment: sweep shapes/dtypes and assert_allclose against the
ref.py oracle for every kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.features import SlayFeatureConfig, init_feature_params
from repro.kernels import feature_map, ops, ref, slay_scan

pytestmark = pytest.mark.kernels


@pytest.mark.parametrize("bh,bk,L,m,dv,chunk", [
    (4, 2, 64, 48, 32, 16),     # GQA g=2
    (2, 2, 32, 16, 16, 8),      # MHA
    (6, 1, 48, 24, 8, 16),      # MQA g=6
    (1, 1, 16, 8, 4, 16),       # single head, chunk == L
    (8, 4, 128, 96, 64, 32),    # bigger
])
def test_scan_kernel_matches_ref(bh, bk, L, m, dv, chunk):
    qf = jax.random.uniform(jax.random.PRNGKey(0), (bh, L, m))
    kf = jax.random.uniform(jax.random.PRNGKey(1), (bk, L, m))
    v = jax.random.normal(jax.random.PRNGKey(2), (bk, L, dv))
    got = slay_scan.causal_linear_attention(qf, kf, v, chunk_size=chunk,
                                            interpret=True)
    want = ref.causal_linear_attention_ref(qf, kf, v, chunk_size=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5,
                               rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scan_kernel_dtypes(dtype):
    qf = jax.random.uniform(jax.random.PRNGKey(0), (2, 32, 16)).astype(dtype)
    kf = jax.random.uniform(jax.random.PRNGKey(1), (2, 32, 16)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 8)).astype(dtype)
    got = slay_scan.causal_linear_attention(qf, kf, v, chunk_size=8,
                                            interpret=True)
    want = ref.causal_linear_attention_ref(qf, kf, v, chunk_size=8)
    assert got.dtype == dtype
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_scan_kernel_rejects_bad_shapes():
    qf = jnp.zeros((3, 32, 16))
    kf = jnp.zeros((2, 32, 16))
    v = jnp.zeros((2, 32, 8))
    with pytest.raises(ValueError):
        slay_scan.causal_linear_attention(qf, kf, v, chunk_size=8,
                                          interpret=True)
    with pytest.raises(ValueError):
        slay_scan.causal_linear_attention(
            jnp.zeros((2, 30, 16)), kf[:, :30], v[:, :30], chunk_size=8,
            interpret=True)


@pytest.mark.parametrize("d,P,D,R,block", [
    (32, 8, 16, 3, 64),
    (16, 4, 8, 2, 32),
    (64, 8, 16, 1, 128),
    (128, 16, 32, 4, 64),
])
def test_feature_map_kernel_matches_ref(d, P, D, R, block):
    cfg = SlayFeatureConfig(head_dim=d, num_anchors=P, num_prf=D,
                            num_quad_nodes=R)
    params = init_feature_params(jax.random.PRNGKey(0), cfg)
    n = block * 2
    u = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    got = feature_map.slay_feature_map(u, params["anchors"],
                                       params["omegas"], cfg,
                                       block_tokens=block, interpret=True)
    want = ref.slay_features_ref(u, params, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_feature_map_kernel_dtypes(dtype):
    cfg = SlayFeatureConfig(head_dim=32)
    params = init_feature_params(jax.random.PRNGKey(0), cfg)
    u = jax.random.normal(jax.random.PRNGKey(1), (64, 32)).astype(dtype)
    got = feature_map.slay_feature_map(u, params["anchors"],
                                       params["omegas"], cfg,
                                       block_tokens=64, interpret=True)
    want = ref.slay_features_ref(u, params, cfg)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_feature_map_kernel_rejects_nonkernelizable():
    cfg = SlayFeatureConfig(head_dim=16, poly_kind="exact")
    params = init_feature_params(jax.random.PRNGKey(0), cfg)
    u = jnp.zeros((32, 16))
    with pytest.raises(ValueError):
        feature_map.slay_feature_map(u, params["anchors"], params["omegas"],
                                     cfg, block_tokens=32, interpret=True)


def test_ops_wrapper_layout_roundtrip():
    """ops.slay_causal_attention must agree with the model-layout oracle
    (GQA layout transposes are the risky part)."""
    B, L, H, hkv, m, dv = 2, 32, 4, 2, 24, 16
    qf = jax.random.uniform(jax.random.PRNGKey(0), (B, L, H, m))
    kf = jax.random.uniform(jax.random.PRNGKey(1), (B, L, hkv, m))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, L, hkv, dv))
    got = ops.slay_causal_attention(qf, kf, v, chunk_size=8, interpret=True)
    from repro.core import linear_attention as la
    want = la.causal_chunked(qf, kf, v, chunk_size=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5,
                               rtol=1e-4)


def test_ops_feature_wrapper_fallback_matches():
    """ops.slay_features: kernel path (interpret) == jnp fallback path."""
    cfg = SlayFeatureConfig(head_dim=16)
    params = init_feature_params(jax.random.PRNGKey(0), cfg)
    u = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 16))  # 256 tokens
    got = ops.slay_features(u, params, cfg, block_tokens=256, interpret=True)
    want = ref.slay_features_ref(u, params, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)


@pytest.mark.parametrize("bh,bk,m,dv", [
    (4, 2, 24, 16),
    (2, 2, 16, 8),
    (6, 1, 48, 32),
    (8, 4, 384, 128),   # production SLAY shape
])
def test_decode_kernel_matches_ref(bh, bk, m, dv):
    from repro.kernels import decode_step as dk
    qf = jax.random.uniform(jax.random.PRNGKey(0), (bh, m))
    kf = jax.random.uniform(jax.random.PRNGKey(1), (bk, m))
    v = jax.random.normal(jax.random.PRNGKey(2), (bk, dv))
    s = jax.random.uniform(jax.random.PRNGKey(3), (bk, m, dv))
    z = jax.random.uniform(jax.random.PRNGKey(4), (bk, m)) + 1.0
    y_k, s_k, z_k = dk.decode_linear_attention(qf, kf, v, s.copy(), z.copy(),
                                               interpret=True)
    y_r, s_r, z_r = ref.decode_linear_attention_ref(qf, kf, v, s, z)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=3e-5,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=3e-5)
    np.testing.assert_allclose(np.asarray(z_k), np.asarray(z_r), atol=3e-5)


@pytest.mark.parametrize("bh,bk,m,dv", [(8, 4, 24, 16), (4, 4, 16, 8)])
def test_decode_kernel_active_mask(bh, bk, m, dv):
    """Continuous-batching pool rows: inactive (drained) slots produce zero
    output and pass their state through bit-identically."""
    from repro.kernels import decode_step as dk
    qf = jax.random.uniform(jax.random.PRNGKey(0), (bh, m))
    kf = jax.random.uniform(jax.random.PRNGKey(1), (bk, m))
    v = jax.random.normal(jax.random.PRNGKey(2), (bk, dv))
    s = jax.random.uniform(jax.random.PRNGKey(3), (bk, m, dv))
    z = jax.random.uniform(jax.random.PRNGKey(4), (bk, m)) + 1.0
    active = jnp.asarray(np.arange(bk) % 2 == 0, jnp.int32)   # evens live
    y_k, s_k, z_k = dk.decode_linear_attention(
        qf, kf, v, s.copy(), z.copy(), active, interpret=True)
    y_r, s_r, z_r = ref.decode_linear_attention_ref(qf, kf, v, s, z, active)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=3e-5,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=3e-5)
    np.testing.assert_allclose(np.asarray(z_k), np.asarray(z_r), atol=3e-5)
    g = bh // bk
    for row in range(bk):
        if row % 2:                    # drained
            np.testing.assert_array_equal(np.asarray(s_k)[row],
                                          np.asarray(s)[row])
            np.testing.assert_array_equal(np.asarray(z_k)[row],
                                          np.asarray(z)[row])
            assert np.all(np.asarray(y_k)[row * g:(row + 1) * g] == 0)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_decode_kernel_stacked_state_in_place(layer):
    """The serving form: the pool state stacked over 3 layers, the layer
    picked by scalar prefetch. Layer ``layer``'s live rows match the oracle
    on s[layer]; its drained rows and every other layer come back
    bit-identical, and drained rows read out zero."""
    from repro.kernels import decode_step as dk
    nl, bh, bk, m, dv = 3, 8, 4, 24, 16
    qf = jax.random.uniform(jax.random.PRNGKey(0), (bh, m))
    kf = jax.random.uniform(jax.random.PRNGKey(1), (bk, m))
    v = jax.random.normal(jax.random.PRNGKey(2), (bk, dv))
    s = jax.random.uniform(jax.random.PRNGKey(3), (nl, bk, m, dv))
    z = jax.random.uniform(jax.random.PRNGKey(4), (nl, bk, m)) + 1.0
    active = jnp.asarray([1, 0, 0, 1], jnp.int32)
    y_k, s_k, z_k = dk.decode_linear_attention(
        qf, kf, v, s, z, active, jnp.int32(layer), interpret=True)
    y_r, s_r, z_r = ref.decode_linear_attention_ref(qf, kf, v, s[layer],
                                                    z[layer], active)
    assert s_k.shape == s.shape and z_k.shape == z.shape
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=3e-5,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_k[layer]), np.asarray(s_r),
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(z_k[layer]), np.asarray(z_r),
                               atol=3e-5)
    g = bh // bk
    for row in (1, 2):                 # drained
        np.testing.assert_array_equal(np.asarray(s_k[layer, row]),
                                      np.asarray(s[layer, row]))
        np.testing.assert_array_equal(np.asarray(z_k[layer, row]),
                                      np.asarray(z[layer, row]))
        assert np.all(np.asarray(y_k)[row * g:(row + 1) * g] == 0)
    for other in set(range(nl)) - {layer}:
        np.testing.assert_array_equal(np.asarray(s_k[other]),
                                      np.asarray(s[other]))
        np.testing.assert_array_equal(np.asarray(z_k[other]),
                                      np.asarray(z[other]))


def test_decode_kernel_sequence_consistency():
    """Repeated kernel decode steps == the chunked causal oracle rows."""
    from repro.kernels import decode_step as dk
    bh = bk = 2
    m, dv, L = 12, 8, 6
    qf = jax.random.uniform(jax.random.PRNGKey(0), (L, bh, m))
    kf = jax.random.uniform(jax.random.PRNGKey(1), (L, bk, m))
    v = jax.random.normal(jax.random.PRNGKey(2), (L, bk, dv))
    full = ref.causal_linear_attention_ref(
        jnp.moveaxis(qf, 0, 1), jnp.moveaxis(kf, 0, 1),
        jnp.moveaxis(v, 0, 1), chunk_size=3)
    s = jnp.zeros((bk, m, dv))
    z = jnp.zeros((bk, m))
    for t in range(L):
        y, s, z = dk.decode_linear_attention(qf[t], kf[t], v[t], s, z,
                                             interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(full[:, t]),
                                   atol=3e-5, rtol=1e-4)
