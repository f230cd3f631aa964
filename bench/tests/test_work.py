"""Work counts against hand arithmetic at small shapes."""
import pytest

from bench import work

SMALL = {"num_layers": 1, "d_model": 4, "num_heads": 2, "num_kv_heads": 2,
         "head_dim": 2, "d_ff": 8, "vocab_size": 10, "gated_mlp": False,
         "attn_kind": "softmax", "slay_anchors": 2, "slay_prf": 2,
         "slay_quad_nodes": 1}


def test_decode_step_call_by_hand():
    w = work.decode_step_call(rows=2, group=1, m=4, dv=2)
    # Per row: S (4x2) and z (4) fp32 read and written: 2 * 12 * 4 = 96 B;
    # q (4), k (4), v (2), y (2) bf16: 12 * 2 = 24 B.
    assert w["bytes"] == 2 * (96 + 24)
    # S += k^T v: 16; z += k: 4; q S: 16; q.z: 8; divide: 2.
    assert w["flops"] == 2 * 46


def test_decode_step_call_at_the_chat_pool():
    # 64 slots x 12 kv heads, m = 384, dv = 64: ~153 MB of fp32 state moved
    # per layer-call, so ~187 us at 819 GB/s, memory bound.
    w = work.decode_step_call(64 * 12, 1, 384, 64)
    assert w["bytes"] == pytest.approx(768 * (2 * (384 * 64 + 384) * 4
                                              + (384 + 384 + 64 + 64) * 2))
    t, side = work.least_time(w["flops"], w["bytes"],
                              {"bf16_flops_per_s": 197e12,
                               "hbm_bytes_per_s": 819e9})
    assert side == "memory" and t == pytest.approx(w["bytes"] / 819e9)


def test_matmul_params_by_hand():
    # q, k, v, o: 4 * 2 * (2 + 2 + 2 + 2) = 64; MLP 4 * 8 * 2 = 64;
    # tied unembedding 10 * 4 = 40.
    assert work.matmul_params(SMALL) == 64 + 64 + 40


def test_attention_flops_by_hand():
    # Softmax at context 5: scores and weighted sum, 4 * 5 * 2 per head.
    assert work.attention_flops_per_token(SMALL, 5) == 2 * 4 * 5 * 2
    slay = dict(SMALL, attn_kind="slay", num_heads=1, num_kv_heads=1)
    # m = 1 * 2 * 2 = 4; features 2Pd + 2Dd + 2m = 8 + 8 + 8 = 24 for q
    # and k; state update 2 m dv + m = 20; readout 2 m dv + 2m = 24.
    assert work.feature_dim(slay) == 4
    assert work.attention_flops_per_token(slay, 5) == 2 * 24 + 20 + 24
    assert work.attention_flops_per_token(slay, 5000) == 92   # no context


def test_token_flops_by_hand():
    assert work.decode_token_flops(SMALL, 5) == 2 * 168 + 80


def test_least_time_picks_the_binding_side():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000.0, 10.0, peak) == (10.0, "compute")
    assert work.least_time(10.0, 1000.0, peak) == (100.0, "memory")
