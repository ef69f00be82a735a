"""Decode attention over an int8-quantized KV cache (Pallas TPU).

The decode-time hot loop for quantized serving: the KV cache is stored
int8 with per-(token, kv-head) scales (produced by the same uniform
quantizer as the weights), halving cache bytes vs bf16 — decode is
memory-bound, so this directly moves the §Roofline memory term.

Schedule: grid (B, K, S/bs). For each (batch, kv-head) the GQA query
group (G = H/K rows) stays resident in VMEM while S streams through in
(bs, hd) int8 tiles; online softmax accumulates in f32 scratch.

Layouts the TPU lowering accepts: per-token scales and positions ride
lane-major — (B, K, 1, S) and (B, 1, S) — so they apply to the (G, bs)
score and probability rows directly: ``q . (k * ks) == (q . k) * ks``
and ``p @ (v * vs) == (p * vs) @ v``, with no (bs,) -> (bs, 1) relayout.
``cur_pos`` is scalar-prefetched into SMEM.

VMEM per step (bs <= 512, hd <= 256, G <= 16):
  q group (G, hd) f32, k/v tiles (bs, hd) int8, scale/pos rows (1, bs),
  m/l (G, 1) and acc (G, hd) f32 scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..spec import describe_kv_decode

Array = jax.Array
MASK = -1e30


def _kv_kernel(cur_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, kpos_ref,
               o_ref, m_ref, l_ref, acc_ref, *, ns: int, window, hd: int):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bs, hd) codes
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (G, bs)
    scores = scores * (ks_ref[0, 0].astype(jnp.float32) / (hd ** 0.5))
    kp = kpos_ref[0]  # (1, bs)
    cur = cur_ref[pl.program_id(0)]
    valid = (kp >= 0) & (kp <= cur)
    if window is not None:
        valid = valid & (cur - kp < window)
    scores = jnp.where(valid, scores, MASK)

    m_prev = m_ref[...]  # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)  # (G, bs)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = p * vs_ref[0, 0].astype(jnp.float32)  # fold v scales into p
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        pv, v_ref[0, 0].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s == ns - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bs", "interpret"))
def kv_decode(q: Array, k8: Array, v8: Array, kscale: Array, vscale: Array,
              kpos: Array, cur_pos: Array, *, window=None, bs: int = 512,
              interpret: bool = False) -> Array:
    """q (B,H,hd); k8/v8 (B,S,K,hd) int8; scales (B,S,K); kpos (B,S) int32;
    cur_pos (B,) int32. Returns (B,H,hd). Tile-math violations raise
    :class:`~repro.kernels.spec.KernelSpecError` naming the shapes."""
    B, H, hd = q.shape
    S, K = k8.shape[1], k8.shape[2]
    bs = min(bs, S)
    sp = describe_kv_decode(q.shape, k8.shape, bs=bs,
                            q_bytes=q.dtype.itemsize)
    G, ns = sp.meta["G"], sp.meta["ns"]

    # regroup: (B, K, G, hd) query groups; (B, K, S, hd) caches;
    # lane-major (B, K, 1, S) scales and (B, 1, S) positions
    qg = q.reshape(B, K, G, hd)
    kt = k8.transpose(0, 2, 1, 3)
    vt = v8.transpose(0, 2, 1, 3)
    kst = kscale.transpose(0, 2, 1)[:, :, None, :]
    vst = vscale.transpose(0, 2, 1)[:, :, None, :]

    out = pl.pallas_call(
        functools.partial(_kv_kernel, ns=ns, window=window, hd=hd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, ns),
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, k, s, cur: (b, k, 0, 0)),
                pl.BlockSpec((1, 1, bs, hd), lambda b, k, s, cur: (b, k, s, 0)),
                pl.BlockSpec((1, 1, bs, hd), lambda b, k, s, cur: (b, k, s, 0)),
                pl.BlockSpec((1, 1, 1, bs), lambda b, k, s, cur: (b, k, 0, s)),
                pl.BlockSpec((1, 1, 1, bs), lambda b, k, s, cur: (b, k, 0, s)),
                pl.BlockSpec((1, 1, bs), lambda b, k, s, cur: (b, 0, s)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda b, k, s, cur: (b, k, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
    )(cur_pos.astype(jnp.int32), qg, kt, vt, kst, vst,
      kpos.astype(jnp.int32)[:, None, :])
    return out.reshape(B, H, hd)
