"""Packed-int weight dequant-matmul Pallas TPU kernels.

The serving GEMMs for BRECQ-quantized models: weights live in HBM as
packed int2/int4/int8 codes (offset-binary, packed along the reduction
axis) with per-group scales; the kernels stream (bkp, bn) packed weight
tiles into VMEM, unpack + dequantize in-register, and accumulate on the
MXU in f32. Three entry points, one per serving tier (see ``ops.qmm``):

  qmatmul          prefill GEMM — grid over (M, N, K) tiles
  qgemv            decode GEMV — M = batch rows (<= 8), no M grid and no
                   M padding
  qmatmul_grouped  stacked MoE experts — a leading expert grid dim
                   consuming (E, K/per, N) nodes directly

Field planes. Packed row r holds the ``per = 8/bits`` codes of K rows
r*per .. r*per+per-1, field i in bits [i*bits, (i+1)*bits). Instead of
interleaving the fields back into K order in-kernel (a sublane relayout
Mosaic does not lower), the wrapper splits x into ``per`` planes
``x3[i, m, r] = x[m, r*per + i]`` and the kernel sums one dot per field:
``x @ W == sum_i x3[i] @ field_i``. Shifts and masks run in int32, the
integer width Mosaic legalizes for vector arithmetic.

Tiling (VMEM working set per step, bn = 128):
  x tile      (per, bm, bkp)        f32/bf16; bkp packed rows on lanes
  w tile      (bkp, bn) int8        <- 8/bits codes per byte
  scale tile  (ng, 1, bn)           ng = scale groups in the k-step
  acc scratch (bm, bn) f32

bkp is a multiple of 128 (the x block's lane dim) or all K/per rows
(``spec._pick_k_step``). Per-channel scales multiply the (bm, bn)
partial sum after the dots; grouped scales (a k-step may span several
groups) dequantize the field tiles, one scale row per group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..spec import (describe_qgemv, describe_qmatmul,
                    describe_qmatmul_grouped)

Array = jax.Array


def _unpack_fields(wp: Array, bits: int) -> list[Array]:
    """(bkp, bn) int8 -> ``per`` (bkp, bn) f32 planes of centred codes;
    plane i row r is the code of K row ``r*per + i``."""
    if bits == 8:
        return [wp.astype(jnp.float32)]
    w = wp.astype(jnp.int32)
    mask, off = (1 << bits) - 1, 1 << (bits - 1)
    return [(((w >> (i * bits)) & mask) - off).astype(jnp.float32)
            for i in range(8 // bits)]


def field_planes(x: Array, per: int) -> Array:
    """(..., M, K) -> (..., per, M, K/per): plane i holds K rows i,
    i + per, i + 2*per, ... — the rows of packed field i."""
    *lead, m, k = x.shape
    return jnp.moveaxis(x.reshape(*lead, m, k // per, per), -1, -3)


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, bits: int, nk: int,
                grouped: bool, k_axis: int, lead: int):
    """One output tile's k-step: acc += sum_i x plane i @ dequant(field
    i). ``lead`` unit block dims (the grouped tier's expert axis) prefix
    every operand; the last k-step writes the (bm, bn) output tile."""
    k = pl.program_id(k_axis)
    at = (0,) * lead

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    wp = w_ref[(*at, Ellipsis)]  # (bkp, bn) int8
    s = s_ref[(*at, Ellipsis)].astype(jnp.float32)  # (ng, 1, bn)
    fields = _unpack_fields(wp, bits)
    if grouped:  # one scale row per group of bkp/ng packed rows
        bkp, bn = wp.shape
        ng = s.shape[0]
        srow = jnp.broadcast_to(s, (ng, bkp // ng, bn)).reshape(bkp, bn)
        fields = [f * srow for f in fields]
    part = None
    for i, f in enumerate(fields):
        d = jax.lax.dot(x_ref[(*at, i, Ellipsis)].astype(jnp.float32), f,
                        preferred_element_type=jnp.float32)
        part = d if part is None else part + d
    if not grouped:  # per-channel: (x @ codes) * s == x @ (codes * s)
        part = part * s[0]
    acc_ref[...] += part

    @pl.when(k == nk - 1)
    def _done():
        o_ref[(*at, Ellipsis)] = acc_ref[...].astype(o_ref.dtype)


def _launch(x: Array, w_packed: Array, scales: Array, sp, *, bits: int,
            index_maps, interpret: bool) -> Array:
    """pallas_call one qmm tier from its spec and (x, w, scales, out)
    index maps; x goes in as field planes, scales as (..., G, 1, N)."""
    m, blocks = sp.meta, sp.blocks
    kernel = functools.partial(
        _qmm_kernel, bits=bits, nk=m["nk"], grouped=scales.shape[-2] > 1,
        k_axis=len(sp.grid) - 1, lead=x.ndim - 2)
    xs, ws, ss, os_ = index_maps
    return pl.pallas_call(
        kernel,
        grid=sp.grid,
        in_specs=[pl.BlockSpec(blocks["x"][0], xs),
                  pl.BlockSpec(blocks["w"][0], ws),
                  pl.BlockSpec(blocks["scales"][0], ss)],
        out_specs=pl.BlockSpec(blocks["out"][0], os_),
        out_shape=jax.ShapeDtypeStruct(x.shape[:-1] + w_packed.shape[-1:],
                                       x.dtype),
        scratch_shapes=[pltpu.VMEM((m["bm"], m["bn"]), jnp.float32)],
        interpret=interpret,
    )(field_planes(x, m["per"]), w_packed, scales[..., None, :])


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "interpret"))
def qmatmul(x: Array, w_packed: Array, scales: Array, *, bits: int,
            bm: int = 128, bn: int = 128, interpret: bool = False) -> Array:
    """x (M, K) @ dequant(w_packed (K/per, N), scales (K/G, N)) -> (M, N).

    Tile-math violations raise :class:`~repro.kernels.spec.KernelSpecError`
    with the offending shapes named (see ``spec.describe_qmatmul``).
    """
    M, _ = x.shape
    N = w_packed.shape[1]
    sp = describe_qmatmul(x.shape, w_packed.shape, scales.shape, bits=bits,
                          bm=min(bm, M), bn=min(bn, N),
                          x_bytes=x.dtype.itemsize)
    sk = scales.shape[0] > 1  # grouped: the scale block walks k
    return _launch(x, w_packed, scales, sp, bits=bits, interpret=interpret,
                   index_maps=(
                       lambda i, j, k: (0, i, k),
                       lambda i, j, k: (k, j),
                       lambda i, j, k: (k if sk else 0, 0, j),
                       lambda i, j, k: (i, j)))


@functools.partial(jax.jit, static_argnames=("bits", "bn", "interpret"))
def qgemv(x: Array, w_packed: Array, scales: Array, *, bits: int,
          bn: int = 128, interpret: bool = False) -> Array:
    """Decode-shaped x (M, K) @ dequant(w_packed, scales) -> (M, N).

    M is the decode batch (a handful of rows): the whole M extent is one
    skinny block — no M grid dim and no zero-row padding to the 8/128
    sublane tile. The grid is (N tiles, k steps) with k innermost: the
    (M, bn) accumulator stays resident in VMEM while the k-loop streams
    the packed weight tiles.
    """
    N = w_packed.shape[1]
    sp = describe_qgemv(x.shape, w_packed.shape, scales.shape, bits=bits,
                        bn=min(bn, N), x_bytes=x.dtype.itemsize)
    sk = scales.shape[0] > 1
    return _launch(x, w_packed, scales, sp, bits=bits, interpret=interpret,
                   index_maps=(
                       lambda j, k: (0, 0, k),
                       lambda j, k: (k, j),
                       lambda j, k: (k if sk else 0, 0, j),
                       lambda j, k: (0, j)))


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "interpret"))
def qmatmul_grouped(x: Array, w_packed: Array, scales: Array, *, bits: int,
                    bm: int = 128, bn: int = 128,
                    interpret: bool = False) -> Array:
    """Grouped expert GEMM: x (E, M, K) @ dequant((E, K/per, N)) -> (E, M, N).

    The expert dim is the leading (outermost) grid axis, so each
    expert's packed codes stream through VMEM exactly once per call —
    the stacked node is consumed directly and no (E, K, N) dequantized
    copy ever exists. M (tokens routed per expert) keeps the true row
    count when it is at most one sublane tile.
    """
    _, M, _ = x.shape
    N = w_packed.shape[2]
    sp = describe_qmatmul_grouped(x.shape, w_packed.shape, scales.shape,
                                  bits=bits, bm=min(bm, M), bn=min(bn, N),
                                  x_bytes=x.dtype.itemsize)
    sk = scales.shape[1] > 1
    return _launch(x, w_packed, scales, sp, bits=bits, interpret=interpret,
                   index_maps=(
                       lambda e, i, j, k: (e, 0, i, k),
                       lambda e, i, j, k: (e, k, j),
                       lambda e, i, j, k: (e, k if sk else 0, 0, j),
                       lambda e, i, j, k: (e, i, j)))
