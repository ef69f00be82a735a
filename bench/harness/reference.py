"""Plain reference of the served model: a dense GQA decoder with RoPE,
RMSNorm and a SwiGLU feed-forward, in straightforward ``jax.numpy``.

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

from . import weights as W

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


def block(dims: W.Dims, w: dict, x, operands=None, kv8: bool = True):
    """One decoder layer over one request's (S, d) hidden states."""
    r = _rounder(operands)

    def mm(a, b):
        return jnp.matmul(r(a), r(b), precision=HIGHEST)

    def es(spec, a, b):
        return jnp.einsum(spec, r(a), r(b), precision=HIGHEST)

    S = x.shape[0]
    H, K, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    h = rmsnorm(x, w["norm1"], dims.norm_eps)
    q = rope(mm(h, w["wq"]).reshape(S, H, hd), dims.rope_theta)
    k = rope(mm(h, w["wk"]).reshape(S, K, hd), dims.rope_theta)
    v = mm(h, w["wv"]).reshape(S, K, hd)
    if kv8:
        k, v = kv_int8(k), kv_int8(v)
    qg = q.reshape(S, K, H // K, hd)
    s = es("qkgd,pkd->kgqp", qg, k) / jnp.sqrt(jnp.asarray(hd, F32))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    a = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = es("kgqp,pkd->qkgd", a, v).reshape(S, H * hd)
    x = x + mm(o, w["wo"])
    h = rmsnorm(x, w["norm2"], dims.norm_eps)
    return x + mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]),
                  w["w_down"])


def served_weights(dims: W.Dims, w_bits: int):
    """(outer, layer) weight makers of the served model: the seed's
    weights under the deployment's round-to-nearest. Both take the seed
    halves as traced arguments, so one compiled program serves every
    seed."""

    def outer(lo, hi):
        o = W.outer(dims, lo, hi)
        emb = rtn(o["embed"], 8)
        return emb, o["final_norm"], (emb.T if dims.tie else rtn(o["head"], 8))

    def layer(lo, hi, i):
        return {n: (a if n.startswith("norm") else rtn(a, w_bits))
                for n, a in W.layer(dims, lo, hi, i).items()}

    return outer, layer


class Reference:
    """The model over given weights: ``outer(*args) -> (embedding (V, d),
    final norm gain, head (d, V))`` and ``layer(*args, i) -> leaves``,
    both traceable in ``args`` and the layer index (unless
    ``traced=False``: then they are called as they are and return
    arrays). Call :meth:`logits`."""

    def __init__(self, dims: W.Dims, outer, layer, args=(), operands=None,
                 kv8: bool = True, traced: bool = True):
        self.dims, self.args = dims, args
        r = _rounder(operands)
        self._outer = jax.jit(outer) if traced else outer
        self._layer = jax.jit(layer) if traced else layer
        self._blocks = jax.jit(lambda w, xs: jax.lax.map(
            lambda x: block(dims, w, x, operands, kv8), xs))
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
        for i in range(self.dims.n_layers):
            xs = self._blocks(self._layer(*self.args, i), xs)
        return self._head(xs, jnp.asarray(positions), g, hw)


def served_gaps(ref_logits, served, valid):
    """Gap by which each served token's reference logit lies below the
    reference's best, as a share of the row's spread (its standard
    deviation over the vocabulary). Arrays (B, P[, V]); invalid rows read 0."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, served[..., None], axis=-1)[..., 0]
    spread = jnp.std(ref_logits, axis=-1)
    return jnp.where(valid, (best - got) / spread, 0.0)
