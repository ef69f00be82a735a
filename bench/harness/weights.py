"""Weights made from the seed, the same for the program and the reference:
the pieces every architecture (``bench/archs/<arch>.py``) shares.

Each leaf has its own key, folded from the seed and the leaf's place, so
one layer can be made again alone (the reference does that, layer by
layer) and comes out bit for bit as in the whole tree the program got.

Distributions: linear weights uniform in +-1/sqrt(fan_in), embedding
and head normal with std 0.02, norm gains uniform in 1 +- 0.1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def base_key(lo, hi):
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _linear(key, shape):
    lim = 1.0 / (shape[0] ** 0.5)
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def _gain(key, shape):
    return 1.0 + jax.random.uniform(key, shape, jnp.float32, -0.1, 0.1)


def layer_key(lo, hi, i):
    """The key layer ``i``'s leaves are folded from."""
    return jax.random.fold_in(base_key(lo, hi), 100 + i)


def leaves(key, shapes: dict) -> dict:
    """Float32 leaves of ``shapes`` ({name: shape}), the j-th from ``key``
    folded with j: a norm gain where the name starts with ``norm``, else
    a linear weight."""
    out = {}
    for j, (leaf, shape) in enumerate(shapes.items()):
        kj = jax.random.fold_in(key, j)
        out[leaf] = _gain(kj, shape) if leaf.startswith("norm") else _linear(kj, shape)
    return out


def outer(dims, lo, hi) -> dict:
    """Embedding (V, d), final norm gain, and the untied head (d, V), from
    ``dims.vocab``, ``dims.d_model`` and ``dims.tie``."""
    b = base_key(lo, hi)
    out = {"embed": 0.02 * jax.random.normal(
               jax.random.fold_in(b, 0), (dims.vocab, dims.d_model), jnp.float32),
           "final_norm": _gain(jax.random.fold_in(b, 2), (dims.d_model,))}
    if not dims.tie:
        out["head"] = 0.02 * jax.random.normal(
            jax.random.fold_in(b, 1), (dims.d_model, dims.vocab), jnp.float32)
    return out
