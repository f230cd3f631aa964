"""Weights made by the benchmark from the seed, on the device, in one
jitted call, in the type they are served in.

The benchmark hands the same tree to the system under test and to the
plain reference, so neither takes weights the other made. The tree is the
model module's (``shapes``, then ``finish``; ``spec.model`` loads the
module a configuration names); the init rule is shared: a matrix is
N(0, 1/fan_in) in bf16, a norm scale zero, a projection N(0, 1) in fp32.
"""
from __future__ import annotations

import functools
from types import ModuleType

import jax
import jax.numpy as jnp
import numpy as np

from bench.generate import rng_for


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, arch_items: tuple, shapes, finish):
    arch = dict(arch_items)
    leaves, treedef = jax.tree.flatten(shapes(arch), is_leaf=_is_leaf)
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
    return finish(jax.tree.unflatten(treedef, out), arch)


def make(arch: dict, seed: int, model: ModuleType):
    """The weights of one run: a pure function of (arch, seed, model)."""
    k = int(rng_for(seed, 2).integers(0, 2**31 - 1))
    return _make(jax.random.PRNGKey(k), tuple(sorted(arch.items())),
                 model.shapes, model.finish)
