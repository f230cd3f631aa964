"""Weights made by the benchmark from the seed, on the device, in one
jitted call, in the type they are served in.

The benchmark hands the same tree to the system under test and to the
plain reference, so neither takes weights the other made. The tree has
the layout the program's transformer reads (stacked layers). Matrices
are N(0, 1/fan_in); the tied embedding is N(0, 0.02^2) as in GPT-2, small
beside what the layers add to the residual stream, so that the next token
depends on the context and not only on the current token (with a unit
embedding, a random tied model's top logit is the current token itself,
and a broken attention path would serve the same tokens).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.generate import rng_for

EMBED_FAN_IN = 2500          # std 1 / sqrt(2500) = 0.02


def shapes(arch: dict) -> dict:
    """Leaf shapes of the parameter tree, each with its init: the fan-in
    of a matrix, 0 for a zero norm scale, -1 for an fp32 projection."""
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


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, arch_items: tuple):
    arch = dict(arch_items)
    spec = shapes(arch)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (shape, fan_in), k in zip(leaves, keys):
        if fan_in == 0:            # norm scales: the model uses (1 + s)
            out.append(jnp.zeros(shape, jnp.bfloat16))
        elif fan_in == -1:         # feature projections, fp32
            out.append(jax.random.normal(k, shape, jnp.float32))
        else:
            w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)
            out.append(w.astype(jnp.bfloat16))
    params = jax.tree.unflatten(treedef, out)
    if "slay" in params:
        a = params["slay"]["anchors"]
        params["slay"]["anchors"] = a / jnp.linalg.norm(a, axis=-1,
                                                        keepdims=True)
        om = params["slay"]["omegas"]
        half = om.shape[0] // 2        # antithetic pairs (omega, -omega)
        params["slay"]["omegas"] = jnp.concatenate([om[:half], -om[:half]])
    return params


def make(arch: dict, seed: int):
    """The weights of one run: a pure function of (arch, seed)."""
    k = int(rng_for(seed, 2).integers(0, 2**31 - 1))
    return _make(jax.random.PRNGKey(k), tuple(sorted(arch.items())))
