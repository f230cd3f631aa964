"""A model family that is not slayformer, for the test that adds a
configuration by files alone: each layer mixes the causal running mean of
the residual stream through a matrix, and the logits carry an output bias,
a leaf slayformer's tree has no counterpart of.

It exports what every model module does: ``shapes``, ``finish`` and
``logits``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def shapes(arch: dict) -> dict:
    L, d, V = arch["num_layers"], arch["d_model"], arch["vocab_size"]
    return {"embed": ((V, d), d), "mix": ((L, d, d), d),
            "out_bias": ((V,), -1)}


def finish(params: dict, arch: dict) -> dict:
    """The output bias is drawn N(0, 1) in fp32 and served at a tenth."""
    params["out_bias"] = params["out_bias"] / 10
    return params


def logits(params, cfg: dict, tokens, idx=None, prec: str = "float32"):
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    if prec != "float32":
        dt = jnp.dtype(prec)
        rnd = lambda x: f32(x).astype(dt).astype(jnp.float32)  # noqa: E731
    else:
        rnd = f32
    emb = rnd(params["embed"])
    x = emb[tokens]
    n = jnp.arange(1, x.shape[0] + 1, dtype=jnp.float32)[:, None]
    with jax.default_matmul_precision("highest"):
        for w in f32(params["mix"]):
            x = x + jnp.tanh((jnp.cumsum(x, 0) / n) @ rnd(w))
        if idx is not None:
            x = x[idx]
        return rnd(x) @ emb.T + f32(params["out_bias"])
