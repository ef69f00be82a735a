"""Static kernel-launch specs + typed tile-math errors.

The single source of truth for the tile math of every Pallas kernel in
``repro.kernels``: each ``describe_*`` function validates one launch's
shapes — raising :class:`KernelSpecError` that *names the offending
shapes* instead of a bare ``assert`` tuple — and returns a
:class:`KernelSpec` describing the grid, the per-operand VMEM block
shapes, and the estimated VMEM footprint of one program instance.

Two consumers share it:

* the kernel wrappers (``qmatmul/kernel.py``, ``kvattn/kernel.py``,
  ``fakequant/kernel.py``) call their ``describe_*`` before
  ``pl.pallas_call`` so a mis-tiled launch fails typed, with shapes
  named, before any tracing happens;
* the static auditor (``repro.analysis.audit.kernel_check``) calls the
  same functions over the registered configs' weight/cache shapes
  without touching a device, so CI catches a BlockSpec that silently
  mis-tiles (or a VMEM blow-up) the moment a kernel or config changes.

The VMEM model is deliberately simple and documented: input blocks are
double-buffered (Pallas pipelines the HBM copies), the output block and
scratch are single-buffered. ``VMEM_BUDGET_BYTES`` is the declared
per-core budget the auditor enforces (16 MB on current TPUs, with a
safety margin left to the compiler).

Every spec also records each operand's full array shape, and
:func:`check_block_tiling` applies the TPU lowering's block rule to it:
a block's last two dims are multiples of (8, 128) or equal the array's
dims (a rank-1 block spans the array or a multiple of 128 words). It is
the rule Mosaic refuses a launch for, so a describe_* that passes here
compiles there as far as block shapes go.
"""
from __future__ import annotations

import dataclasses
import math

# Declared VMEM budget per program instance. TPU cores have ~16 MB of
# VMEM; the compiler needs headroom for semaphores/pipelining, so the
# audit budget is deliberately below the hardware size.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


class KernelSpecError(ValueError):
    """A kernel launch's shapes violate its tiling contract.

    Raised (with the failing shapes named) instead of the bare
    ``assert``s the kernels used to carry — catchable by the static
    auditor and by users feeding odd shapes. Mirrors the
    ``PackedNodeError`` pattern in ``qmatmul/ops.py``.
    """


class BlockTilingError(KernelSpecError):
    """A block shape the TPU lowering refuses: its last two dims are
    neither multiples of (8, 128) nor the array's own dims."""


def _check(cond: bool, kernel: str, msg: str) -> None:
    if not cond:
        raise KernelSpecError(f"{kernel}: {msg}")


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Static description of one Pallas kernel launch."""

    kernel: str
    grid: tuple[int, ...]
    blocks: dict  # operand name -> (block shape, dtype bytes)
    scratch: dict  # scratch name -> (shape, dtype bytes)
    meta: dict  # kernel-specific derived tiling (bk, nk, ...)
    arrays: dict = dataclasses.field(default_factory=dict)  # name -> shape

    @property
    def vmem_bytes(self) -> int:
        """Estimated VMEM per program instance: double-buffered input
        blocks + single-buffered output/scratch."""
        total = 0
        for name, (shape, nbytes) in self.blocks.items():
            mult = 1 if name.startswith("out") else 2
            total += mult * math.prod(shape) * nbytes
        for shape, nbytes in self.scratch.values():
            total += math.prod(shape) * nbytes
        return total

    @property
    def num_programs(self) -> int:
        return math.prod(self.grid)

    def check_budget(self, budget: int = VMEM_BUDGET_BYTES) -> None:
        _check(self.vmem_bytes <= budget, self.kernel,
               f"estimated VMEM {self.vmem_bytes} bytes/program exceeds "
               f"the declared budget {budget} (grid {self.grid}, blocks "
               f"{ {k: v[0] for k, v in self.blocks.items()} })")


def check_block_tiling(spec: KernelSpec) -> KernelSpec:
    """Apply the TPU lowering's block rule to every operand of ``spec``.

    Rank >= 2: the last dim is a multiple of 128 or the array's last
    dim, and the second-to-last a multiple of 8 or the array's. Rank 1:
    the block spans the array or a multiple of 128 * (4 / dtype bytes)
    elements. Raises :class:`BlockTilingError` naming the operand."""
    for name, (block, nbytes) in spec.blocks.items():
        array = spec.arrays.get(name)
        if array is None:
            continue
        if len(block) == 1:
            tile = 128 * max(1, 4 // nbytes)
            ok = block[0] == array[0] or block[0] % tile == 0
            rule = f"a multiple of {tile} or the array's {array[0]}"
        else:
            ok = ((block[-1] == array[-1] or block[-1] % 128 == 0)
                  and (block[-2] == array[-2] or block[-2] % 8 == 0))
            rule = "multiples of (8, 128) or the array's last two dims"
        if not ok:
            raise BlockTilingError(
                f"{spec.kernel}: operand {name!r} block {tuple(block)} over "
                f"array {tuple(array)} is not TPU-tileable (last dims must "
                f"be {rule})")
    return spec


def aligned_tile(dim: int, cap: int, align: int) -> int:
    """Largest divisor of ``dim`` that is <= ``cap`` and a multiple of
    ``align``; ``dim`` itself (one block spanning the whole axis, which
    the block rule always accepts) when no such divisor exists."""
    for d in range(min(dim, cap) - min(dim, cap) % align, 0, -align):
        if dim % d == 0:
            return d
    return dim


def _bits_per(kernel: str, bits: int) -> int:
    _check(bits in (2, 4, 8), kernel,
           f"container bits must be 2, 4 or 8, got {bits}")
    return 8 // bits


def _pick_k_step(kernel: str, K: int, G: int, per: int) -> tuple[int, int]:
    """(bkp, nk): packed rows per k-step and the number of k-steps.

    The kernels take activations as ``per`` field planes (per, M, K/per)
    (see ``qmatmul/kernel.py``), so a k-step's packed rows are the x
    block's lane dim: a multiple of 128, or all K/per rows in one step.
    A grouped step also covers whole scale groups (g/per packed rows
    each), applying one scale row per group inside the step."""
    rows = K // per
    _check(rows * per == K, kernel,
           f"K={K} is not a multiple of the packing factor per={per} "
           f"({8 // per}-bit codes)")
    _check(K % G == 0, kernel,
           f"K={K} does not split into G={G} scale groups")
    align = 128
    if G > 1:
        g = K // G
        _check(g % per == 0, kernel,
               f"scale group {g} is not a multiple of the packing factor "
               f"per={per} ({8 // per}-bit codes)")
        align = math.lcm(128, g // per)
    bkp = aligned_tile(rows, 512, align)
    return bkp, rows // bkp


def _describe_qmm(name: str, x_shape, wp_shape, scales_shape, *, bits: int,
                  bm: int, bn: int, x_bytes: int) -> KernelSpec:
    """Shared tile math of the three qmm tiers; a leading expert dim on
    every operand (stacked experts) becomes the outermost grid axis."""
    per = _bits_per(name, bits)
    lead = tuple(x_shape[:-2])
    M, K = x_shape[-2:]
    rows, N = wp_shape[-2:]
    G = scales_shape[-2]
    _check(tuple(wp_shape[:-2]) == lead and tuple(scales_shape[:-2]) == lead,
           name, f"expert axes disagree: x {tuple(x_shape)}, codes "
           f"{tuple(wp_shape)}, scales {tuple(scales_shape)}")
    _check(rows * per == K, name,
           f"packed rows {rows} x {per} values/byte != K={K} "
           f"(codes {tuple(wp_shape)}, x {tuple(x_shape)}, bits={bits})")
    _check(scales_shape[-1] == N, name,
           f"scales {tuple(scales_shape)} do not span N={N} columns")
    bkp, nk = _pick_k_step(name, K, G, per)
    _check(M % bm == 0, name, f"M={M} is not a multiple of bm={bm}")
    _check(N % bn == 0, name, f"N={N} is not a multiple of bn={bn}")
    ng = G // nk if G > 1 else 1  # scale rows (groups) per k-step
    one = (1,) * len(lead)
    return check_block_tiling(KernelSpec(
        kernel=name, grid=(*lead, M // bm, N // bn, nk),
        blocks={"x": ((*one, per, bm, bkp), x_bytes),
                "w": ((*one, bkp, bn), 1),
                "scales": ((*one, ng, 1, bn), 4),
                "out": ((*one, bm, bn), x_bytes)},
        arrays={"x": (*lead, per, M, rows), "w": (*lead, rows, N),
                "scales": (*lead, G, 1, N), "out": (*lead, M, N)},
        scratch={"acc": ((bm, bn), 4)},
        meta={"bk": bkp * per, "bkp": bkp, "nk": nk, "ng": ng, "bm": bm,
              "bn": bn, "per": per}))


def qmm_row_tile(M: int, K: int, G: int, *, bits: int, bn: int,
                 x_bytes: int = 4) -> int:
    """Rows per ``qmatmul``/``qmatmul_grouped`` program: 128 when M tiles
    by it, else one 8-row sublane tile (the caller zero-pads M), halved
    while the launch overruns the VMEM budget — a k-step spans all K/per
    packed rows when they have no 128-lane divisor (d_ff 10944)."""
    bm = 128 if M % 128 == 0 else 8
    wp = (K // _bits_per("qmatmul", bits), bn)
    while bm > 8 and _describe_qmm(
            "qmatmul", (bm, K), wp, (G, bn), bits=bits, bm=bm, bn=bn,
            x_bytes=x_bytes).vmem_bytes > VMEM_BUDGET_BYTES:
        bm //= 2
    return bm


def describe_qmatmul(x_shape, wp_shape, scales_shape, *, bits: int,
                     bm: int, bn: int, x_bytes: int = 4) -> KernelSpec:
    """Validate + describe a ``qmatmul`` (prefill GEMM) launch.

    x (M, K) @ dequant(wp (K*bits/8, N), scales (K/G, N)) -> (M, N),
    grid (M/bm, N/bn, nk).
    """
    return _describe_qmm("qmatmul", x_shape, wp_shape, scales_shape,
                         bits=bits, bm=bm, bn=bn, x_bytes=x_bytes)


def describe_qgemv(x_shape, wp_shape, scales_shape, *, bits: int,
                   bn: int, x_bytes: int = 4) -> KernelSpec:
    """Validate + describe a ``qgemv`` (decode GEMV) launch.

    The whole M extent (decode batch rows) is one skinny block; grid
    (N/bn, nk) with the (M, bn) accumulator VMEM-resident.
    """
    sp = _describe_qmm("qgemv", x_shape, wp_shape, scales_shape, bits=bits,
                       bm=x_shape[0], bn=bn, x_bytes=x_bytes)
    return dataclasses.replace(sp, grid=sp.grid[1:])


def describe_qmatmul_grouped(x_shape, wp_shape, scales_shape, *, bits: int,
                             bm: int, bn: int, x_bytes: int = 4) -> KernelSpec:
    """Validate + describe a ``qmatmul_grouped`` (stacked experts) launch.

    x (E, M, K) @ dequant((E, K*bits/8, N)) -> (E, M, N), expert-major
    grid (E, M/bm, N/bn, nk).
    """
    return _describe_qmm("qmatmul_grouped", x_shape, wp_shape, scales_shape,
                         bits=bits, bm=bm, bn=bn, x_bytes=x_bytes)


def kv_stream_tile(S: int) -> int:
    """Cache rows per ``kv_decode`` step: the scale and position blocks
    put the stream on lanes, so a multiple of 128 (at most 512) that
    divides S, else all S rows in one step."""
    return aligned_tile(S, 512, 128)


def describe_kv_decode(q_shape, k8_shape, *, bs: int,
                       q_bytes: int = 4) -> KernelSpec:
    """Validate + describe a ``kv_decode`` (int8-KV attention) launch.

    q (B, H, hd) over int8 caches (B, S, K_heads, hd); grid (B, K, S/bs)
    with the (G, hd) query group resident while S streams. Scales and
    positions ride lane-major ((B, K, 1, S) and (B, 1, S)); ``cur_pos``
    is scalar-prefetched into SMEM and takes no VMEM block.
    """
    name = "kv_decode"
    B, H, hd = q_shape
    S, K = k8_shape[1], k8_shape[2]
    _check(K > 0 and H % K == 0, name,
           f"query heads H={H} not divisible into kv heads K={K} "
           f"(q {tuple(q_shape)}, cache {tuple(k8_shape)})")
    G = H // K
    _check(S % bs == 0, name,
           f"cache length S={S} is not a multiple of the stream tile "
           f"bs={bs} (cache {tuple(k8_shape)})")
    return check_block_tiling(KernelSpec(
        kernel=name, grid=(B, K, S // bs),
        blocks={"q": ((1, 1, G, hd), q_bytes), "k": ((1, 1, bs, hd), 1),
                "v": ((1, 1, bs, hd), 1), "kscale": ((1, 1, 1, bs), 4),
                "vscale": ((1, 1, 1, bs), 4), "kpos": ((1, 1, bs), 4),
                "out": ((1, 1, G, hd), q_bytes)},
        arrays={"q": (B, K, G, hd), "k": (B, K, S, hd), "v": (B, K, S, hd),
                "kscale": (B, K, 1, S), "vscale": (B, K, 1, S),
                "kpos": (B, 1, S), "out": (B, K, G, hd)},
        scratch={"m": ((G, 1), 4), "l": ((G, 1), 4), "acc": ((G, hd), 4)},
        meta={"bs": bs, "ns": S // bs, "G": G}))


def fakequant_tiles(K: int, N: int) -> tuple[int, int]:
    """(bk, bn) for a ``fakequant`` launch over a (K, N) weight: at most
    256 x 256, with bk halved while a full-width bn (N without a 128-lane
    divisor) overruns the VMEM budget."""
    bn = aligned_tile(N, 256, 128)
    cap = 256
    while True:
        bk = aligned_tile(K, cap, 8)
        if cap <= 8 or describe_fakequant(
                (K, N), (1, N), bk=bk, bn=bn).vmem_bytes <= VMEM_BUDGET_BYTES:
            return bk, bn
        cap //= 2


def describe_fakequant(w_shape, scale_shape, *, bk: int, bn: int,
                       w_bytes: int = 4) -> KernelSpec:
    """Validate + describe a ``fakequant`` (AdaRound forward) launch.

    w, v (K, N) with a (1, N) or (K, N) scale; grid (K/bk, N/bn).
    """
    name = "fakequant"
    K, N = w_shape
    _check(K % bk == 0, name, f"K={K} is not a multiple of bk={bk} "
           f"(w {tuple(w_shape)})")
    _check(N % bn == 0, name, f"N={N} is not a multiple of bn={bn} "
           f"(w {tuple(w_shape)})")
    _check(scale_shape[0] in (1, K) and scale_shape[1] == N, name,
           f"scale {tuple(scale_shape)} must be (1, {N}) or ({K}, {N})")
    per_row = scale_shape[0] != 1
    return check_block_tiling(KernelSpec(
        kernel=name, grid=(K // bk, N // bn),
        blocks={"w": ((bk, bn), w_bytes), "v": ((bk, bn), w_bytes),
                "scale": ((bk if per_row else 1, bn), 4),
                "out": ((bk, bn), w_bytes)},
        arrays={"w": (K, N), "v": (K, N), "scale": tuple(scale_shape),
                "out": (K, N)},
        scratch={},
        meta={"bk": bk, "bn": bn, "per_row": per_row}))
