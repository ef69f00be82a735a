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

Tiling. ``spec.qmm_tiles`` picks (bm, bkp, bn) from the call's shape,
so each packed weight byte is fetched and unpacked once per call, in
grid steps large enough to hide their fixed cost:
  bm          all M rows up to 256 (a 32-slot decode step, a 32-row
              prefill chunk) — one row block; above, 128 where M tiles
              by it
  w tile      (bkp, bn) int8, up to 1 MB: aligned divisors of K/per and
              N (multiples of 128, else the whole axis), e.g. 1024 x 1024
              for a 6144-wide W4 layer and 2048 x 384 for a 92544-way
              8-bit head (N = 723 x 128)
  x block     (per, bm, K/per) f32/bf16, every packed row of the row
              block, fetched once (index constant in j and k); the
              kernel slices the k-step's lanes with ``pl.ds``. Where the
              VMEM model will not hold it, (per, bm, bkp) walking k
  scale tile  (ng, 1, bn)           ng = scale groups in the k-step
  acc scratch (bm, bn) f32
Inside a step the kernel unpacks and dots the weight tile ``rs`` packed
rows at a time (``fori_loop``; about 128 KB of codes a slice), so the
f32 field planes stay small whatever the step; ``spec._describe_qmm``
counts them in the VMEM model. Per-channel scales multiply the (bm, bn)
partial sum after the dots; grouped scales (a slice may span several
groups) dequantize the field tiles, one scale row per group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..spec import (describe_qgemv, describe_qmatmul,
                    describe_qmatmul_grouped, qmm_tiles)

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


def _ds(start, size: int):
    """``pl.ds`` with the alignment a traced start keeps (every traced
    offset here is a multiple of ``size``)."""
    if not isinstance(start, int):
        start = pl.multiple_of(start, size)
    return pl.ds(start, size)


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, bits: int, nk: int,
                rs: int, x_resident: bool, grouped: bool, k_axis: int,
                lead: int):
    """One output tile's k-step: acc += sum_i x plane i @ dequant(field
    i), over the (bkp, bn) weight tile ``rs`` packed rows at a time.
    ``lead`` unit block dims (the grouped tier's expert axis) prefix
    every operand; the last k-step writes the (bm, bn) output tile."""
    k = pl.program_id(k_axis)
    at = (0,) * lead
    bkp, bn = w_ref.shape[-2:]
    ns = bkp // rs
    x0 = k * bkp if x_resident and nk > 1 else 0  # the step's x lanes

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def slice_dot(r, part):
        off = r * rs
        fields = _unpack_fields(w_ref[(*at, _ds(off, rs), slice(None))],
                                bits)
        if grouped:  # one scale row per group of gr packed rows
            gr = bkp // s_ref.shape[-3]
            s = s_ref[(*at, _ds(r * (rs // gr), rs // gr), Ellipsis)]
            srow = jnp.broadcast_to(s.astype(jnp.float32),
                                    (rs // gr, gr, bn)).reshape(rs, bn)
            fields = [f * srow for f in fields]
        for i, f in enumerate(fields):
            xs = x_ref[(*at, i, slice(None), _ds(x0 + off, rs))]
            part = part + jax.lax.dot(xs.astype(jnp.float32), f,
                                      preferred_element_type=jnp.float32)
        return part

    part = jnp.zeros(acc_ref.shape, jnp.float32)
    if ns == 1:
        part = slice_dot(0, part)
    else:  # unrolled, so one slice's unpack overlaps another's dots
        part = jax.lax.fori_loop(0, ns, slice_dot, part, unroll=True)
    if not grouped:  # per-channel: (x @ codes) * s == x @ (codes * s)
        part = part * s_ref[(*at, 0)].astype(jnp.float32)
    acc_ref[...] += part

    @pl.when(k == nk - 1)
    def _done():
        o_ref[(*at, Ellipsis)] = acc_ref[...].astype(o_ref.dtype)


def _launch(x: Array, w_packed: Array, scales: Array, sp, *, bits: int,
            index_maps, interpret: bool) -> Array:
    """pallas_call one qmm tier from its spec and (x, w, scales, out)
    index maps; x goes in as field planes, scales as (..., G, 1, N).
    Each x index map takes ``kx``, the factor on its k index: 0 when the
    x block holds every packed row (fetched once per row block), else
    1."""
    m, blocks = sp.meta, sp.blocks
    kernel = functools.partial(
        _qmm_kernel, bits=bits, nk=m["nk"], rs=m["rs"],
        x_resident=m["x_resident"], grouped=scales.shape[-2] > 1,
        k_axis=len(sp.grid) - 1, lead=x.ndim - 2)
    xs, ws, ss, os_ = index_maps
    kx = 0 if m["x_resident"] else 1
    return pl.pallas_call(
        kernel,
        grid=sp.grid,
        in_specs=[pl.BlockSpec(blocks["x"][0], functools.partial(xs, kx)),
                  pl.BlockSpec(blocks["w"][0], ws),
                  pl.BlockSpec(blocks["scales"][0], ss)],
        out_specs=pl.BlockSpec(blocks["out"][0], os_),
        out_shape=jax.ShapeDtypeStruct(x.shape[:-1] + w_packed.shape[-1:],
                                       x.dtype),
        scratch_shapes=[pltpu.VMEM((m["bm"], m["bn"]), jnp.float32)],
        interpret=interpret,
    )(field_planes(x, m["per"]), w_packed, scales[..., None, :])


def _tiles(x: Array, w_packed: Array, scales: Array, bits: int, bm, bkp,
           bn) -> tuple[int, int, int]:
    """The launch's (bm, bkp, bn): ``spec.qmm_tiles`` for the shape,
    with any tile the caller gave in its place."""
    picked = qmm_tiles(x.shape[-2], x.shape[-1], w_packed.shape[-1],
                       scales.shape[-2], bits=bits,
                       x_bytes=x.dtype.itemsize)
    return tuple(p if t is None else t for p, t in zip(picked, (bm, bkp, bn)))


@functools.partial(jax.jit,
                   static_argnames=("bits", "bm", "bkp", "bn", "interpret"))
def qmatmul(x: Array, w_packed: Array, scales: Array, *, bits: int,
            bm: int | None = None, bkp: int | None = None,
            bn: int | None = None, interpret: bool = False) -> Array:
    """x (M, K) @ dequant(w_packed (K/per, N), scales (K/G, N)) -> (M, N).

    Tiles not given come from ``spec.qmm_tiles``. Tile-math violations
    raise :class:`~repro.kernels.spec.KernelSpecError` with the
    offending shapes named (see ``spec.describe_qmatmul``).
    """
    bm, bkp, bn = _tiles(x, w_packed, scales, bits, bm, bkp, bn)
    sp = describe_qmatmul(x.shape, w_packed.shape, scales.shape, bits=bits,
                          bm=bm, bkp=bkp, bn=bn, x_bytes=x.dtype.itemsize)
    sk = scales.shape[0] > 1  # grouped: the scale block walks k
    return _launch(x, w_packed, scales, sp, bits=bits, interpret=interpret,
                   index_maps=(
                       lambda kx, i, j, k: (0, i, k * kx),
                       lambda i, j, k: (k, j),
                       lambda i, j, k: (k if sk else 0, 0, j),
                       lambda i, j, k: (i, j)))


@functools.partial(jax.jit, static_argnames=("bits", "bkp", "bn", "interpret"))
def qgemv(x: Array, w_packed: Array, scales: Array, *, bits: int,
          bkp: int | None = None, bn: int | None = None,
          interpret: bool = False) -> Array:
    """Decode-shaped x (M, K) @ dequant(w_packed, scales) -> (M, N).

    M is the decode batch (a handful of rows): the whole M extent is one
    skinny block — no M grid dim and no zero-row padding to the 8/128
    sublane tile. The grid is (N tiles, k steps) with k innermost: the
    (M, bn) accumulator stays resident in VMEM while the k-loop streams
    the packed weight tiles.
    """
    _, bkp, bn = _tiles(x, w_packed, scales, bits, None, bkp, bn)
    sp = describe_qgemv(x.shape, w_packed.shape, scales.shape, bits=bits,
                        bkp=bkp, bn=bn, x_bytes=x.dtype.itemsize)
    sk = scales.shape[0] > 1
    return _launch(x, w_packed, scales, sp, bits=bits, interpret=interpret,
                   index_maps=(
                       lambda kx, j, k: (0, 0, k * kx),
                       lambda j, k: (k, j),
                       lambda j, k: (k if sk else 0, 0, j),
                       lambda j, k: (0, j)))


@functools.partial(jax.jit,
                   static_argnames=("bits", "bm", "bkp", "bn", "interpret"))
def qmatmul_grouped(x: Array, w_packed: Array, scales: Array, *, bits: int,
                    bm: int | None = None, bkp: int | None = None,
                    bn: int | None = None, interpret: bool = False) -> Array:
    """Grouped expert GEMM: x (E, M, K) @ dequant((E, K/per, N)) -> (E, M, N).

    The expert dim is the leading (outermost) grid axis, so each
    expert's packed codes stream through VMEM exactly once per call —
    the stacked node is consumed directly and no (E, K, N) dequantized
    copy ever exists. M (tokens routed per expert) keeps the true row
    count up to ``spec.QMM_ONE_BLOCK_ROWS``.
    """
    bm, bkp, bn = _tiles(x, w_packed, scales, bits, bm, bkp, bn)
    sp = describe_qmatmul_grouped(x.shape, w_packed.shape, scales.shape,
                                  bits=bits, bm=bm, bkp=bkp, bn=bn,
                                  x_bytes=x.dtype.itemsize)
    sk = scales.shape[1] > 1
    return _launch(x, w_packed, scales, sp, bits=bits, interpret=interpret,
                   index_maps=(
                       lambda kx, e, i, j, k: (e, 0, i, k * kx),
                       lambda e, i, j, k: (e, k, j),
                       lambda e, i, j, k: (e, k if sk else 0, 0, j),
                       lambda e, i, j, k: (e, i, j)))
