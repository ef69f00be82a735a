"""Weights made from the seed, the same for the program and the reference.

Each leaf has its own key, folded from the seed and the leaf's place, so
one layer can be made again alone (the reference does that, layer by
layer) and comes out bit for bit as in the whole tree the program got.

Distributions: linear weights uniform in +-1/sqrt(fan_in), embedding
and head normal with std 0.02, norm gains uniform in 1 +- 0.1.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_gate", "w_up",
                "w_down")


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense GQA decoder with a SwiGLU feed-forward."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tie: bool
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        heads = c["num_attention_heads"]
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=heads, n_kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim", c["hidden_size"] // heads),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   tie=c["tie_word_embeddings"],
                   rope_theta=float(c["rope_theta"]),
                   norm_eps=float(c["rms_norm_eps"]))

    def leaf_shape(self, leaf: str) -> tuple[int, ...]:
        d, q, kv, f = (self.d_model, self.n_heads * self.head_dim,
                       self.n_kv_heads * self.head_dim, self.d_ff)
        return {"norm1": (d,), "norm2": (d,), "wq": (d, q), "wk": (d, kv),
                "wv": (d, kv), "wo": (q, d), "w_gate": (d, f), "w_up": (d, f),
                "w_down": (f, d)}[leaf]


def base_key(lo, hi):
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _linear(key, shape):
    lim = 1.0 / (shape[0] ** 0.5)
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def _gain(key, shape):
    return 1.0 + jax.random.uniform(key, shape, jnp.float32, -0.1, 0.1)


def layer(dims: Dims, lo, hi, i) -> dict:
    """Layer ``i``'s float32 leaves."""
    k = jax.random.fold_in(base_key(lo, hi), 100 + i)
    out = {}
    for j, leaf in enumerate(LAYER_LEAVES):
        kj = jax.random.fold_in(k, j)
        shape = dims.leaf_shape(leaf)
        out[leaf] = _gain(kj, shape) if leaf.startswith("norm") else _linear(kj, shape)
    return out


def outer(dims: Dims, lo, hi) -> dict:
    """Embedding (V, d), final norm gain, and the untied head (d, V)."""
    b = base_key(lo, hi)
    out = {"embed": 0.02 * jax.random.normal(
               jax.random.fold_in(b, 0), (dims.vocab, dims.d_model), jnp.float32),
           "final_norm": _gain(jax.random.fold_in(b, 2), (dims.d_model,))}
    if not dims.tie:
        out["head"] = 0.02 * jax.random.normal(
            jax.random.fold_in(b, 1), (dims.d_model, dims.vocab), jnp.float32)
    return out


def program_tree(dims: Dims, lo, hi) -> dict:
    """The whole float32 tree in the layout of ``repro.models.LM``."""
    o = outer(dims, lo, hi)
    layers = [layer(dims, lo, hi, i) for i in range(dims.n_layers)]

    def stack(leaf):
        return jnp.stack([lay[leaf] for lay in layers])

    tree = {"embed": {"table": o["embed"]}, "final_norm": {"g": o["final_norm"]},
            "body": {"sub0": {
                "norm1": {"g": stack("norm1")},
                "attn": {n: {"w": stack(n)} for n in ("wq", "wk", "wv", "wo")},
                "norm2": {"g": stack("norm2")},
                "mlp": {n: {"w": stack(n)} for n in ("w_gate", "w_up", "w_down")},
            }}}
    if not dims.tie:
        tree["head"] = {"w": o["head"]}
    return tree


def served_maker(dims: Dims, pack):
    """One jitted program from the seed to the served weights:
    ``pack`` (the program's packing transform) over the float32 tree, so
    the float32 leaves live only inside the program."""
    return jax.jit(lambda lo, hi: pack(program_tree(dims, lo, hi)))
