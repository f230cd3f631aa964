"""The slayformer family in both attention kinds, as the benchmark states
it: the weights tree it serves (``shapes``, ``finish``) and its plain
reference (``logits``). A configuration file names this module under
``"model"``.

The reference is straight jax.numpy over one sequence at a time: no
kernels, no cache, no batching tricks, no chunked scan. Causal SLAY
attention is written in its quadratic form, y_i = sum_{j<=i} <Psi(q_i),
Psi(k_j)> v_j / (sum_{j<=i} <Psi(q_i), Psi(k_j)> + delta), which equals
the linear-time recurrence exactly in real arithmetic. It imports nothing
of the program: the feature map, the quadrature nodes and the layer
equations are written out here from the paper (Eqs. 2, 8-11) and from the
model definition the configuration file states (RMSNorm, RoPE, SiLU MLP,
tied embeddings).

Precision: ``prec="float32"`` is the reference, every matmul at
``highest``. Any lower ``prec`` (the control) rounds every matmul operand
(weights and activations) to that type and accumulates in fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


# The tied embedding is N(0, 0.02^2) as in GPT-2, small beside what the
# layers add to the residual stream, so that the next token depends on the
# context and not only on the current token (with a unit embedding, a
# random tied model's top logit is the current token itself, and a broken
# attention path would serve the same tokens).
EMBED_FAN_IN = 2500          # std 1 / sqrt(2500) = 0.02


def shapes(arch: dict) -> dict:
    """Leaf shapes of the parameter tree, each with its init: the fan-in
    of a matrix, 0 for a zero norm scale, -1 for an fp32 projection. The
    tree has the layout the program's transformer reads (stacked
    layers)."""
    L, d, H = arch["num_layers"], arch["d_model"], arch["num_heads"]
    Hkv, dh, f = arch["num_kv_heads"], arch["head_dim"], arch["d_ff"]
    V = arch["vocab_size"]
    layers = {
        "pre_attn": ((L, d), 0), "pre_mlp": ((L, d), 0),
        "attn": {"wq": ((L, d, H, dh), d), "wk": ((L, d, Hkv, dh), d),
                 "wv": ((L, d, Hkv, dh), d), "wo": ((L, H, dh, d), H * dh)},
        "mlp": {"up": ((L, d, f), d), "down": ((L, f, d), f)},
    }
    tree = {"embed": ((V, d), EMBED_FAN_IN), "final_norm": ((d,), 0),
            "layers": layers}
    if arch["attn_kind"] == "slay":
        tree["slay"] = {"anchors": ((arch["slay_anchors"], dh), -1),
                        "omegas": ((arch["slay_prf"], dh), -1)}
    return tree


def finish(params: dict, arch: dict) -> dict:
    """The drawn tree made into SLAY's feature bank: anchors unit rows,
    PRF omegas in antithetic pairs (omega, -omega)."""
    if "slay" in params:
        a = params["slay"]["anchors"]
        params["slay"]["anchors"] = a / jnp.linalg.norm(a, axis=-1,
                                                        keepdims=True)
        om = params["slay"]["omegas"]
        half = om.shape[0] // 2
        params["slay"]["omegas"] = jnp.concatenate([om[:half], -om[:half]])
    return params


def quadrature(num_nodes: int, eps: float) -> tuple[np.ndarray, np.ndarray]:
    """Gauss-Laguerre nodes/weights for x^2/(C - 2x) = int e^{-sC} x^2
    e^{2sx} ds, C = 2 + eps (paper Eq. 8): s_r = t_r / C, w_r = a_r / C."""
    t, a = np.polynomial.laguerre.laggauss(num_nodes)
    c = 2.0 + eps
    return t / c, a / c


def _rounder(prec: str):
    if prec == "float32":
        return lambda x: x.astype(jnp.float32)
    dt = jnp.dtype(prec)
    return lambda x: x.astype(jnp.float32).astype(dt).astype(jnp.float32)


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * (1.0 + scale)


def _rope(x, theta: float):
    """x (L, H, dh); rotate halves by position-dependent angles."""
    L, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs      # (L, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def slay_features(u, anchors, omegas, s, w):
    """Psi(u) for u (..., d): unit-normalize (Eq. 2), anchor features
    (u.a_p)^2 / sqrt(P), positive random features exp(sqrt(2 s_r) w.u -
    s_r) / sqrt(D), fused per node by a Kronecker product scaled by
    sqrt(w_r) (Eq. 10)."""
    u = u * jax.lax.rsqrt(jnp.sum(u * u, -1, keepdims=True) + 1e-6)
    P, D = anchors.shape[0], omegas.shape[0]
    poly = jnp.square(u @ anchors.T) / np.sqrt(P)                 # (..., P)
    proj = u @ omegas.T                                           # (..., D)
    s = jnp.asarray(s, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    prf = jnp.exp(jnp.sqrt(2.0 * s)[:, None] * proj[..., None, :]
                  - s[:, None]) / np.sqrt(D)                      # (...,R,D)
    fused = (jnp.sqrt(w)[:, None, None] * poly[..., None, :, None]
             * prf[..., :, None, :])                              # (...,R,P,D)
    return fused.reshape(*u.shape[:-1], -1)


def _attention(q, k, v, kind: str, feat, rnd, delta: float):
    """Causal attention of one sequence, one head at a time.
    q, k, v (L, H, dh) -> (L, H, dh)."""
    L = q.shape[0]
    causal = jnp.tril(jnp.ones((L, L), bool))

    def head(qkv):
        qh, kh, vh = qkv                                          # (L, dh)
        if kind == "slay":
            a = rnd(feat(qh)) @ rnd(feat(kh)).T                  # (L, L)
            a = jnp.where(causal, a, 0.0)
            return (a @ vh) / (jnp.sum(a, -1, keepdims=True) + delta)
        sc = (qh @ kh.T) / np.sqrt(qh.shape[-1])
        sc = jnp.where(causal, sc, -jnp.inf)
        return rnd(jax.nn.softmax(sc, axis=-1)) @ vh

    ys = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                            jnp.moveaxis(v, 1, 0)))
    return jnp.moveaxis(ys, 0, 1)


def hidden(params, cfg: dict, tokens, prec: str = "float32"):
    """Final-normed hidden states (L, d) of one token sequence (L,)."""
    arch, slay = cfg["arch"], cfg.get("slay", {})
    rnd = _rounder(prec)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    kind = arch["attn_kind"]
    feat = None
    if kind == "slay":
        s, w = quadrature(arch["slay_quad_nodes"], slay["eps"])
        an, om = f32(params["slay"]["anchors"]), f32(params["slay"]["omegas"])
        feat = functools.partial(slay_features, anchors=an, omegas=om, s=s,
                                 w=w)
    x = rnd(f32(params["embed"]))[tokens]
    lay = jax.tree.map(f32, params["layers"])
    theta = float(arch["rope_theta"])

    def layer(x, lp):
        xa = rnd(_rmsnorm(x, lp["pre_attn"]))
        q = _rope(jnp.einsum("ld,dhk->lhk", xa, rnd(lp["attn"]["wq"])), theta)
        k = _rope(jnp.einsum("ld,dhk->lhk", xa, rnd(lp["attn"]["wk"])), theta)
        v = jnp.einsum("ld,dhk->lhk", xa, rnd(lp["attn"]["wv"]))
        y = _attention(rnd(q), rnd(k), rnd(v), kind, feat, rnd,
                       slay.get("delta", 0.0))
        x = x + jnp.einsum("lhk,hkd->ld", rnd(y), rnd(lp["attn"]["wo"]))
        xm = rnd(_rmsnorm(x, lp["pre_mlp"]))
        h = rnd(jax.nn.silu(xm @ rnd(lp["mlp"]["up"])))
        return x + h @ rnd(lp["mlp"]["down"]), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(jax.checkpoint(layer), x, lay)
    return _rmsnorm(x, f32(params["final_norm"]))


def logits(params, cfg: dict, tokens, idx=None, prec: str = "float32"):
    """Next-token logits (tied embeddings) of one sequence: (L, V), or
    only at the positions ``idx`` when given."""
    rnd = _rounder(prec)
    h = hidden(params, cfg, tokens, prec)
    if idx is not None:
        h = h[idx]
    with jax.default_matmul_precision("highest"):
        return rnd(h) @ rnd(jnp.asarray(params["embed"], jnp.float32)).T
