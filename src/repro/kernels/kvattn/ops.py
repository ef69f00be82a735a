"""Public wrapper for int8-KV decode attention + cache quantization."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..spec import kv_stream_tile
from .kernel import kv_decode
from .ref import kv_decode_ref

Array = jax.Array


def quantize_kv(k: Array, v: Array) -> tuple[Array, Array, Array, Array]:
    """Quantize KV caches to int8 with per-(token, head) scales.

    Args:
      k, v: float caches of shape (B, S, K_heads, head_dim).

    Returns:
      ``(k8, v8, kscale, vscale)`` — int8 codes with the input shapes and
      f32 absmax/127 scales of shape (B, S, K_heads).
    """
    def q(x):
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
        scale = jnp.maximum(amax / 127.0, 1e-8)
        codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                         -128, 127).astype(jnp.int8)
        return codes, scale

    k8, ks = q(k)
    v8, vs = q(v)
    return k8, v8, ks, vs


def attend_int8(q: Array, k8: Array, v8: Array, kscale: Array, vscale: Array,
                kpos: Array, cur_pos: Array, *, window=None,
                backend: str = "auto") -> Array:
    """Single-step decode attention over an int8-quantized KV cache.

    Args:
      q: current-step queries of shape (B, H, head_dim).
      k8, v8: int8 cache codes of shape (B, S, K_heads, head_dim)
        (``H % K_heads == 0`` for grouped-query sharing).
      kscale, vscale: f32 dequant scales of shape (B, S, K_heads) from
        :func:`quantize_kv`.
      kpos: cache-slot positions, (B, S) int32; negative marks an empty
        slot.
      cur_pos: current decode position per sequence, (B,) int32; slots
        with ``kpos > cur_pos`` (or empty) are masked out.
      window: optional sliding-window size in tokens (positions older
        than ``cur_pos - window`` are masked); ``None`` = full causal.
      backend: ``'auto'`` (Pallas on TPU, XLA reference elsewhere),
        ``'pallas'``, or ``'xla'``.

    Returns:
      Attention output of shape (B, H, head_dim), in ``q``'s dtype.
    """
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "xla":
        return kv_decode_ref(q, k8, v8, kscale, vscale, kpos, cur_pos, window)
    interpret = jax.default_backend() != "tpu"
    return kv_decode(q, k8, v8, kscale, vscale, kpos, cur_pos,
                     window=window, bs=kv_stream_tile(k8.shape[1]),
                     interpret=interpret)
