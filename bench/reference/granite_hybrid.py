"""The granite-4.0-h family (``granitemoehybrid`` without experts) as the
benchmark states it: the weights tree it serves (``shapes``, ``finish``)
and its plain reference (``logits``). A configuration file names this
module under ``"model"``.

The model: tokens embedded and scaled by ``embedding_multiplier``; then
per layer x += r * mixer(norm(x)) and x += r * mlp(norm(x)), r the
``residual_multiplier``, the mixer a Mamba-2 block or, at the layers
``layer_types`` names "attention", causal GQA softmax attention with no
positional encoding and scores scaled by ``attention_multiplier``; the MLP
is SiLU-gated; a final RMSNorm, then logits from the tied embedding
divided by ``logits_scaling``.

The reference is straight jax.numpy over one sequence at a time: no
kernels, no cache, no chunked scan. The Mamba-2 block is written as its
per-token recurrence, h <- exp(dt A) h + dt x B^T, y = h C + D x, after a
causal depthwise conv with bias, then a gated RMSNorm over the whole inner
width (one group) and the out projection. Attention is quadratic, one head
at a time. It imports nothing of the program.

Where it departs from the source: RMSNorm scales are stored as s and
applied as (1 + s), the repository's convention (the source stores and
applies w = 1 + s); the MLP's input projection is two matrices, gate and
up, where the source keeps one matrix of twice the width and splits it;
the weights are random (``finish``); the residual stream is fp32 where the
source runs in bf16. The source's dt limit (0, inf) clamps nothing and is
left out.

Precision: ``prec="float32"`` is the reference, every matmul at
``highest``. Any lower ``prec`` (the control) rounds every matmul and
contraction operand (weights and activations, the SSM state at its
readout) to that type and accumulates in fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# The tied embedding is N(0, 0.001^2). With a tied embedding the current
# token's own logit is 12 |e| / |x| standard deviations of the others'
# (12 the embedding multiplier, |x| the residual stream's norm at the last
# layer): near the ~4.4 of the largest of 100k others, a random model's
# top token is the current token itself, whatever the context, and a
# broken state path serves the same tokens. At 0.001 it is 1.8 at two
# layers and 0.3 at forty, so the served tokens follow the context.
EMBED_FAN_IN = 1_000_000         # std 1 / sqrt(1e6) = 0.001
# Mamba-2's own init (its reference code): A ~ U[1, 16], so A_log = log A;
# dt log-uniform on [1e-3, 1e-1], so dt_bias = softplus^-1(dt); D = 1.
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)


def layer_kinds(arch: dict) -> list[str]:
    """The kind of each layer: ``layer_types`` is the source's list joined
    by commas (the weights call hashes the values of ``arch``, and a list
    does not hash); a stack cut in depth keeps its first ``num_layers``."""
    return arch["layer_types"].split(",")[:arch["num_layers"]]


def _dims(arch: dict) -> dict:
    d = arch["d_model"]
    di = arch["ssm_expand"] * d
    ng, ds = arch["ssm_ngroups"], arch["ssm_state"]
    return dict(d=d, di=di, nh=di // arch["ssm_head_dim"],
                hd=arch["ssm_head_dim"], ng=ng, ds=ds,
                W=arch["ssm_conv_width"], conv=di + 2 * ng * ds)


def shapes(arch: dict) -> dict:
    """Leaf shapes of the parameter tree, each with its init: the fan-in
    of a bf16 N(0, 1/fan_in) matrix, 0 for a zero norm scale. The tree has
    the program's layout: the norms and MLP of every layer stacked under
    ``layers``, the Mamba mixers stacked under ``ssd`` and the attention
    projections under ``attn``, each over its own kind's layers."""
    L, V = arch["num_layers"], arch["vocab_size"]
    kinds = layer_kinds(arch)
    nm, na = kinds.count("mamba"), kinds.count("attention")
    H, Hkv, dh, f = (arch["num_heads"], arch["num_kv_heads"],
                     arch["head_dim"], arch["d_ff"])
    m = _dims(arch)
    d, di, nh = m["d"], m["di"], m["nh"]
    tree = {
        "embed": ((V, d), EMBED_FAN_IN), "final_norm": ((d,), 0),
        "layers": {"pre_mix": ((L, d), 0), "pre_mlp": ((L, d), 0),
                   "mlp": {"gate": ((L, d, f), d), "up": ((L, d, f), d),
                           "down": ((L, f, d), f)}},
    }
    if nm:
        # a_log, dt_bias and d_skip are drawn N(0, 1) here and set by
        # finish; the conv bias is drawn as its weights are.
        tree["ssd"] = {
            "in_proj": ((nm, d, 2 * di + 2 * m["ng"] * m["ds"] + nh), d),
            "conv_w": ((nm, m["W"], m["conv"]), m["W"]),
            "conv_b": ((nm, m["conv"]), m["W"]),
            "a_log": ((nm, nh), 1), "dt_bias": ((nm, nh), 1),
            "d_skip": ((nm, nh), 1), "norm": ((nm, di), 0),
            "out_proj": ((nm, di, d), di)}
    if na:
        tree["attn"] = {"wq": ((na, d, H, dh), d),
                        "wk": ((na, d, Hkv, dh), d),
                        "wv": ((na, d, Hkv, dh), d),
                        "wo": ((na, H, dh, d), H * dh)}
    return tree


def finish(params: dict, arch: dict) -> dict:
    """Mamba-2's init of A, dt and D, from the N(0, 1) draws of their
    leaves: each draw's normal CDF is a U(0, 1) draw."""
    if "ssd" in params:
        p = dict(params["ssd"])
        u_a = jax.scipy.special.ndtr(p["a_log"].astype(jnp.float32))
        a = A_RANGE[0] + (A_RANGE[1] - A_RANGE[0]) * u_a
        u_dt = jax.scipy.special.ndtr(p["dt_bias"].astype(jnp.float32))
        lo, hi = np.log(DT_RANGE[0]), np.log(DT_RANGE[1])
        dt = jnp.exp(lo + (hi - lo) * u_dt)
        p["a_log"] = jnp.log(a).astype(jnp.bfloat16)
        p["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.bfloat16)
        p["d_skip"] = jnp.ones_like(p["d_skip"])
        params = dict(params, ssd=p)
    return params


def _rounder(prec: str):
    if prec == "float32":
        return lambda x: x.astype(jnp.float32)
    dt = jnp.dtype(prec)
    return lambda x: x.astype(jnp.float32).astype(dt).astype(jnp.float32)


def _rmsnorm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _mamba(p, x, arch: dict, rnd):
    """One Mamba-2 block over one sequence x (L, d) -> (L, d)."""
    m = _dims(arch)
    L, di, nh, hd, ds, W = (x.shape[0], m["di"], m["nh"], m["hd"], m["ds"],
                            m["W"])
    zxbcdt = rnd(x) @ rnd(p["in_proj"])
    z, u, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + m["conv"]],
                zxbcdt[:, di + m["conv"]:])
    # Causal depthwise conv of width W with bias, then SiLU.
    up = jnp.pad(u, ((W - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(up[i:i + L] * p["conv_w"][i] for i in range(W))
                    + p["conv_b"])
    xs = u[:, :di].reshape(L, nh, hd)
    g = nh // m["ng"]
    b = jnp.repeat(u[:, di:di + m["ng"] * ds].reshape(L, m["ng"], ds), g,
                   axis=1)                                    # (L, nh, ds)
    c = jnp.repeat(u[:, di + m["ng"] * ds:].reshape(L, m["ng"], ds), g,
                   axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                   # (L, nh)
    a = -jnp.exp(p["a_log"])                                  # (nh,)

    def step(h, t):
        x_t, b_t, c_t, dt_t = t
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + dt_t[:, None, None] * rnd(x_t)[:, :, None]
             * rnd(b_t)[:, None, :])
        return h, jnp.einsum("hds,hs->hd", rnd(h), rnd(c_t))

    _, y = jax.lax.scan(step, jnp.zeros((nh, hd, ds), jnp.float32),
                        (xs, b, c, dt))
    y = (y + p["d_skip"][:, None] * xs).reshape(L, di) * jax.nn.silu(z)
    y = _rmsnorm(y, p["norm"], arch["norm_eps"])
    return rnd(y) @ rnd(p["out_proj"])


def _attention(p, x, arch: dict, rnd):
    """Causal GQA softmax attention over one sequence x (L, d), no
    positional encoding, scores times ``attention_multiplier``."""
    L = x.shape[0]
    g = arch["num_heads"] // arch["num_kv_heads"]
    xr = rnd(x)
    q = jnp.einsum("ld,dhk->lhk", xr, rnd(p["wq"]))
    k = jnp.repeat(jnp.einsum("ld,dhk->lhk", xr, rnd(p["wk"])), g, axis=1)
    v = jnp.repeat(jnp.einsum("ld,dhk->lhk", xr, rnd(p["wv"])), g, axis=1)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def head(qkv):
        qh, kh, vh = qkv                                      # (L, dh)
        sc = (rnd(qh) @ rnd(kh).T) * arch["attention_multiplier"]
        sc = jnp.where(causal, sc, -jnp.inf)
        return rnd(jax.nn.softmax(sc, axis=-1)) @ rnd(vh)

    y = jnp.moveaxis(jax.lax.map(head, (jnp.moveaxis(q, 1, 0),
                                        jnp.moveaxis(k, 1, 0),
                                        jnp.moveaxis(v, 1, 0))), 0, 1)
    return jnp.einsum("lhk,hkd->ld", rnd(y), rnd(p["wo"]))


def hidden(params, cfg: dict, tokens, prec: str = "float32"):
    """Final-normed hidden states (L, d) of one token sequence (L,)."""
    arch = cfg["arch"]
    rnd = _rounder(prec)
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    eps, r = arch["norm_eps"], arch["residual_multiplier"]
    kinds = np.array([t == "attention" for t in layer_kinds(arch)],
                     np.int32)
    idx = np.zeros_like(kinds)
    for k in (0, 1):
        idx[kinds == k] = np.arange(int(np.sum(kinds == k)))
    x = rnd(f32(params["embed"]))[tokens] * arch["embedding_multiplier"]

    def layer(x, xs):
        lp, kind, j = f32(xs[0]), xs[1], xs[2]
        h = _rmsnorm(x, lp["pre_mix"], eps)
        branches = []
        if "ssd" in params:
            branches.append(lambda: _mamba(f32(jax.tree.map(
                lambda a: a[j], params["ssd"])), h, arch, rnd))
        if "attn" in params:
            branches.append(lambda: _attention(f32(jax.tree.map(
                lambda a: a[j], params["attn"])), h, arch, rnd))
        y = (jax.lax.switch(kind, branches) if len(branches) == 2
             else branches[0]())
        x = x + r * y
        xm = rnd(_rmsnorm(x, lp["pre_mlp"], eps))
        mp = lp["mlp"]
        hm = rnd(jax.nn.silu(xm @ rnd(mp["gate"])) * (xm @ rnd(mp["up"])))
        return x + r * (hm @ rnd(mp["down"])), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(jax.checkpoint(layer), x,
                            (params["layers"], kinds, idx))
    return _rmsnorm(x, f32(params["final_norm"]), eps)


def logits(params, cfg: dict, tokens, idx=None, prec: str = "float32"):
    """Next-token logits (tied embeddings, divided by ``logits_scaling``)
    of one sequence: (L, V), or only at the positions ``idx`` when
    given."""
    rnd = _rounder(prec)
    h = hidden(params, cfg, tokens, prec)
    if idx is not None:
        h = h[idx]
    with jax.default_matmul_precision("highest"):
        out = rnd(h) @ rnd(jnp.asarray(params["embed"], jnp.float32)).T
    return out / cfg["arch"]["logits_scaling"]
