"""Operations and bytes the algorithms need, from shapes alone.

These are the yardstick of every roofline and utilization the benchmark
reports: the least work a call has to do, counted from the published
sizes, never from what a program happens to do. FLOPs count a multiply
and an add as two; bytes count HBM reads plus writes.
"""
from __future__ import annotations

F32, BF16 = 4, 2


def feature_dim(arch: dict) -> int:
    """m = R * P * D, the SLAY feature width (paper Eq. 10)."""
    return (arch["slay_quad_nodes"] * arch["slay_anchors"]
            * arch["slay_prf"])


def decode_step_call(rows: int, group: int, m: int, dv: int) -> dict:
    """One ``decode_step`` kernel call over ``rows`` kv-head rows.

    Per row: read the fp32 state S (m x dv) and z (m), write both back
    updated; read q features (group x m), k features (m) and v (dv) in
    bf16; write y (group x dv) in bf16. FLOPs: S += k^T v (2 m dv),
    z += k (m), numerator q S (2 group m dv), denominator q.z
    (2 group m), division (group dv)."""
    state = 2 * (m * dv + m) * F32
    io = (group * m + m + dv + group * dv) * BF16
    flops = 2 * m * dv + m + 2 * group * m * dv + 2 * group * m + group * dv
    return {"flops": rows * flops, "bytes": rows * (state + io)}


def matmul_params(arch: dict) -> int:
    """Weights that every token multiplies once: attention projections,
    MLP and the (tied) unembedding; the embedding gather is not a matmul.
    """
    d, L = arch["d_model"], arch["num_layers"]
    H, Hkv, dh = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    attn = d * dh * (2 * H + 2 * Hkv)
    mlp = d * arch["d_ff"] * (3 if arch.get("gated_mlp") else 2)
    return L * (attn + mlp) + arch["vocab_size"] * d


def attention_flops_per_token(arch: dict, context: int) -> int:
    """Attention FLOPs of one token at ``context`` tokens seen, all layers.

    SLAY decode: features of q and k (anchors 2Pd, omegas 2Dd, Kronecker
    and scale 2m each), state update 2 m dv + m, readout 2 m dv + 2m per
    query head. Softmax: scores and weighted sum, 4 context dh per head.
    """
    L, H, dh = arch["num_layers"], arch["num_heads"], arch["head_dim"]
    Hkv = arch["num_kv_heads"]
    if arch["attn_kind"] == "slay":
        m, P, D = feature_dim(arch), arch["slay_anchors"], arch["slay_prf"]
        feat = 2 * P * dh + 2 * D * dh + 2 * m
        per = ((H + Hkv) * feat + Hkv * (2 * m * dh + m)
               + H * (2 * m * dh + 2 * m))
    else:
        per = H * 4 * context * dh
    return L * per


def decode_token_flops(arch: dict, context: int) -> int:
    """Model FLOPs of one decoded token at ``context`` tokens seen."""
    return 2 * matmul_params(arch) + attention_flops_per_token(arch, context)


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Roofline bound of some work and which side binds it."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
