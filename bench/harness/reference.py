"""The plain reference of a served model, in straightforward
``jax.numpy``: what every architecture shares. Each architecture module
(``bench/archs/<arch>.py``) brings its own layer (``block``) and its
reference weights; this module walks them.

It imports nothing of the program. It makes its weights again from the
seed (``weights.py``) and applies the deployment's stated quantization
itself:

* linear weights: symmetric round-to-nearest at ``w_bits``, one scale
  per output column (absmax over the reduction axis / qmax);
* embedding table and untied head: the same at 8 bits (the embedding's
  columns are the model dimension, so its scales run over the vocab);
* K and V: int8 per (token, kv head), scale absmax / 127, the scale kept
  as float16, every attention read (the current token's too) sees the
  dequantized values.

It computes in float32, its matmuls at ``Precision.HIGHEST``. The
control (``operands="float8_e4m3fn"``) is the same mathematics with
every matmul operand rounded to float8: one precision below the served
model's, whose float32 dots run on the TPU at the default precision,
with bfloat16 operands and float32 accumulation.

It runs after the window, layer by layer over a batch of requests, one
request at a time inside a layer, so it fits beside nothing else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rtn(w, bits: int):
    """Dequantized round-to-nearest of a (K, N) weight, scale per column."""
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True) / qmax, 1e-8)
    return jnp.clip(jnp.round(w / s), -qmax - 1, qmax) * s


def kv_int8(x):
    """(S, K, hd) -> int8 codes times a float16 scale per (token, head)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-8)
    codes = jnp.clip(jnp.round(x / s), -128, 127)
    return codes * s.astype(jnp.float16).astype(F32)


HIGHEST = jax.lax.Precision.HIGHEST


def _rounder(operands):
    """Rounds a matmul operand to ``operands`` and back (identity for None)."""
    if operands is None:
        return lambda a: a
    dt = jnp.dtype(operands)
    return lambda a: a.astype(dt).astype(F32)


def rmsnorm(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def rope(x, theta: float):
    """(S, H, hd), rotate-half convention, positions 0..S-1."""
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None].astype(x.dtype), jnp.sin(ang)[:, None].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


class Reference:
    """The model of architecture module ``arch`` under the deployment's
    quantization at ``w_bits``, its weights made again from the seed
    halves ``args`` (``arch.reference_weights``). ``arch.block`` applies
    layer ``i``. Call :meth:`logits`.

    Layers are grouped by ``arch.layer_kind(dims, i)`` (every layer is of
    kind 0 where the module has none): a module's layer maker and block
    may give each kind its own leaves and code, and each kind compiles
    once, with the layer index traced."""

    def __init__(self, arch, dims, w_bits: int, args=(), operands=None,
                 kv8: bool = True):
        self.arch, self.dims, self.args = arch, dims, args
        outer, layer = arch.reference_weights(dims, w_bits)
        kind = getattr(arch, "layer_kind", lambda d, i: 0)
        self.kinds = [kind(dims, i) for i in range(arch.reference_layers(dims))]
        r = _rounder(operands)
        self._outer = jax.jit(outer)
        self._layer = jax.jit(layer, static_argnames="kind")
        self._blocks = jax.jit(lambda i, w, xs, kind: jax.lax.map(
            lambda x: arch.block(dims, i, w, x, operands, kv8, kind=kind), xs),
            static_argnames="kind")
        self._embed = jax.jit(lambda e, t: e[t])

        def head(xs, idx, g, hw):
            xl = jnp.take_along_axis(xs, idx[..., None], axis=1)
            xl = rmsnorm(xl, g, dims.norm_eps)
            return jnp.einsum("bpd,dv->bpv", r(xl), r(hw), precision=HIGHEST)

        self._head = jax.jit(head)

    def logits(self, tokens, positions):
        """tokens (B, S) int32 (padding after each request's end is
        harmless: attention is causal); positions (B, P) the rows whose
        next-token logits are wanted. Returns (B, P, V) float32."""
        emb, g, hw = self._outer(*self.args)
        xs = self._embed(emb, jnp.asarray(tokens))
        del emb
        for i, k in enumerate(self.kinds):
            xs = self._blocks(i, self._layer(*self.args, i, kind=k), xs, kind=k)
        return self._head(xs, jnp.asarray(positions), g, hw)


def served_gaps(ref_logits, served, valid):
    """Gap by which each served token's reference logit lies below the
    reference's best, as a share of the row's spread (its standard
    deviation over the vocabulary). Arrays (B, P[, V]); invalid rows read 0."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, served[..., None], axis=-1)[..., 0]
    spread = jnp.std(ref_logits, axis=-1)
    return jnp.where(valid, (best - got) / spread, 0.0)
