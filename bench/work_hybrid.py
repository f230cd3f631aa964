"""Operations and bytes of a mixed Mamba-2 / attention stack's decode
(``bench/reference/granite_hybrid.py``), from its published sizes alone.

The yardstick of ``mfu.granite`` and ``mbu.granite``: the least work a
decoded token and a decode tick need, never what the program happens to
do. FLOPs count a multiply and an add as two; bytes count HBM reads plus
writes.
"""
from __future__ import annotations

F32, BF16 = 4, 2


def layer_counts(arch: dict) -> tuple[int, int]:
    """(Mamba layers, attention layers) of the first ``num_layers`` of
    ``layer_types`` (the source's list joined by commas)."""
    kinds = arch["layer_types"].split(",")[:arch["num_layers"]]
    return kinds.count("mamba"), kinds.count("attention")


def mamba_dims(arch: dict) -> dict:
    d = arch["d_model"]
    di = arch["ssm_expand"] * d
    ng, ds = arch["ssm_ngroups"], arch["ssm_state"]
    return {"d_inner": di, "heads": di // arch["ssm_head_dim"],
            "head_dim": arch["ssm_head_dim"], "state": ds,
            "conv_dim": di + 2 * ng * ds, "proj": 2 * di + 2 * ng * ds
            + di // arch["ssm_head_dim"]}


def matmul_params(arch: dict) -> int:
    """Weights that every token multiplies once: the Mamba in and out
    projections, the attention projections, every layer's gated MLP and
    the tied unembedding; the embedding gather is not a matmul."""
    d, f, V = arch["d_model"], arch["d_ff"], arch["vocab_size"]
    H, Hkv, dh = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    nm, na = layer_counts(arch)
    m = mamba_dims(arch)
    mamba = d * m["proj"] + m["d_inner"] * d
    attn = d * dh * (2 * H + 2 * Hkv)
    mlp = 3 * d * f
    return nm * mamba + na * attn + (nm + na) * mlp + V * d


def weight_bytes(arch: dict) -> int:
    """bf16 bytes of every weight a decode tick reads once: the matmul
    weights, the conv weights and bias, A, dt bias, D and every norm
    scale."""
    d, L = arch["d_model"], arch["num_layers"]
    nm, _ = layer_counts(arch)
    m = mamba_dims(arch)
    small = nm * ((arch["ssm_conv_width"] + 1) * m["conv_dim"]
                  + 3 * m["heads"] + m["d_inner"]) + 2 * L * d + d
    return BF16 * (matmul_params(arch) + small)


def ssm_state_bytes(arch: dict) -> int:
    """fp32 SSM state of one slot over every Mamba layer."""
    nm, _ = layer_counts(arch)
    m = mamba_dims(arch)
    return nm * m["heads"] * m["head_dim"] * m["state"] * F32


def conv_tail_bytes(arch: dict) -> int:
    """fp32 conv tail (the last W - 1 conv inputs) of one slot over every
    Mamba layer."""
    nm, _ = layer_counts(arch)
    return nm * (arch["ssm_conv_width"] - 1) * mamba_dims(arch)[
        "conv_dim"] * F32


def kv_row_bytes(arch: dict) -> int:
    """One token's bf16 K and V rows over every attention layer."""
    _, na = layer_counts(arch)
    return na * 2 * arch["num_kv_heads"] * arch["head_dim"] * BF16


def decode_token_flops(arch: dict, context: int) -> int:
    """Model FLOPs of one decoded token at ``context`` tokens seen: 2 per
    matmul weight; the SSM update and readout, 5 per state element (decay
    multiply, the dt x B outer product and its add, the C readout's
    multiply-add); attention's scores and weighted sum, 4 context dh per
    query head."""
    nm, na = layer_counts(arch)
    m = mamba_dims(arch)
    ssm = nm * m["heads"] * 5 * m["head_dim"] * m["state"]
    attn = na * 4 * context * arch["head_dim"] * arch["num_heads"]
    return 2 * matmul_params(arch) + ssm + attn


def decode_token_bytes(arch: dict, context: int) -> int:
    """Bytes one live slot adds to a decode tick at ``context`` tokens
    seen: its SSM state and conv tail read and written back, its KV rows
    read over the context and the new row written."""
    return (2 * (ssm_state_bytes(arch) + conv_tail_bytes(arch))
            + (context + 1) * kv_row_bytes(arch))


def delivered_contexts(ctx) -> tuple[list, list[int]]:
    """The decode steps of a run's window, and the context of each token
    they delivered (its prompt plus the tokens before it)."""
    import bisect
    st = [s for s in ctx["steps"] if s.decode_ticks]
    starts = [s.t0 for s in st]
    rec, out = ctx["rec"], []
    for rid, ts in rec.token_times.items():
        plen = len(rec.req[rid].prompt)
        for j, t in enumerate(ts[1:], start=1):
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t <= st[i].t1:
                out.append(plen + j)
    return st, out
