"""Static Pallas kernel audit: tile math + VMEM over registered configs.

No device and no weights: each arch's params tree comes from
``jax.eval_shape(model.init, ...)`` and the packed serving shapes from
``jax.eval_shape`` over ``deploy.pack.quantize_tree`` (shape-driven by
design), then every packed leaf is swept through the same
``kernels.spec.describe_*`` functions the kernel wrappers call — with
the tiles ``kernels.spec.qmm_tiles`` picks for them and the ragged-N
padding the ops layer applies
(``qmatmul/ops.py`` ``_qmm_2d``/``_qmm_grouped``, ``kvattn/ops.py``
``attend_int8``). A launch the runtime would attempt that fails its
tiling contract, or whose estimated VMEM exceeds
:data:`~repro.kernels.spec.VMEM_BUDGET_BYTES`, becomes a
:class:`~.rules.Violation`.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ...kernels.spec import KernelSpecError
from .rules import Violation, register_catalog_rule

register_catalog_rule(
    "kernel_tile_divisibility", "kernel",
    "Every kernel launch the serving/calibration path would issue over a "
    "registered config's shapes must satisfy its grid/BlockSpec tiling "
    "contract (describe_* raises KernelSpecError naming the shapes).")
register_catalog_rule(
    "kernel_vmem_budget", "kernel",
    "The estimated VMEM footprint of one program instance (double-"
    "buffered input blocks + output/scratch) must stay under the "
    "declared per-core budget for every audited launch.")

# Decode batch rows and canonical KV cache length for the sweep; tiles
# come from the pickers the ops wrappers call.
DECODE_M = 4
PREFILL_M = 128
KV_SEQ = 512


def _iter_packed(fp, packed, path=()):
    """Yield (path, logical_K, wp_shape, qscale_shape) for every packed
    node, walking the FP shape tree (for K) and the packed tree in
    lockstep."""
    if isinstance(packed, dict):
        if "w" in packed and "qscale" in packed and hasattr(
                packed["w"], "shape"):
            fw = fp["w"] if isinstance(fp, dict) and "w" in fp else None
            if fw is not None and getattr(fw, "ndim", 0) >= 2:
                yield ("/".join(path), fw.shape[-2], packed["w"].shape,
                       packed["qscale"].shape)
            return
        for k in packed:
            yield from _iter_packed(
                fp.get(k) if isinstance(fp, dict) else None,
                packed[k], path + (str(k),))


def _sweep_leaf(arch: str, path: str, K: int, wp_shape, qs_shape,
                emit: Callable[[str, str, str], None]) -> None:
    """Audit every launch the qmm dispatch would issue for one packed
    leaf (decode + prefill tiers; grouped for stacked experts)."""
    from ...kernels.spec import (describe_qgemv, describe_qmatmul,
                                 describe_qmatmul_grouped, qmm_tiles)

    # strip the scan-stack dim: the runtime slices one layer per step
    if len(wp_shape) >= 3 and len(qs_shape) == len(wp_shape):
        wp_shape, qs_shape = wp_shape[1:], qs_shape[1:]
    rows, N = wp_shape[-2], wp_shape[-1]
    if rows == 0 or K % rows or K // rows not in (1, 2, 4):
        emit("kernel_tile_divisibility", f"{arch}:{path}",
             f"packed rows {rows} are not a 1/2/4-per-byte view of "
             f"K={K} (codes {tuple(wp_shape)})")
        return
    bits = 8 // (K // rows)
    npad = N + (-N) % 128 if N >= 128 else N  # ops._pad_cols
    wp2 = (rows, npad)
    qs2 = (qs_shape[-2], npad)
    subject = f"{arch}:{path}"
    launches = []
    if len(wp_shape) == 2:
        launches = [
            ("decode", DECODE_M, lambda m, bm, **t: describe_qgemv(
                (m, K), wp2, qs2, bits=bits, **t)),
            ("prefill", PREFILL_M, lambda m, **t: describe_qmatmul(
                (m, K), wp2, qs2, bits=bits, **t)),
        ]
    elif len(wp_shape) == 3:
        E = wp_shape[0]
        wp3, qs3 = (E,) + wp2, (E,) + qs2
        launches = [
            (f"grouped-{tier}", m, lambda m, **t: describe_qmatmul_grouped(
                (E, m, K), wp3, qs3, bits=bits, **t))
            for tier, m in (("decode", DECODE_M), ("prefill", PREFILL_M))]
    for tier, m, describe in launches:
        try:
            bm, bkp, bn = qmm_tiles(m, K, npad, qs2[0], bits=bits)
            sp = describe(m, bm=bm, bkp=bkp, bn=bn)
        except KernelSpecError as e:
            emit("kernel_tile_divisibility", f"{subject}[{tier}]", str(e))
            continue
        try:
            sp.check_budget()
        except KernelSpecError as e:
            emit("kernel_vmem_budget", f"{subject}[{tier}]", str(e))


def _sweep_kv(arch: str, cfg, emit: Callable[[str, str, str], None]) -> None:
    from ...kernels.spec import describe_kv_decode, kv_stream_tile

    S = KV_SEQ
    bs = kv_stream_tile(S)
    q_shape = (DECODE_M, cfg.n_heads, cfg.hd)
    k8_shape = (DECODE_M, S, cfg.n_kv_heads, cfg.hd)
    try:
        sp = describe_kv_decode(q_shape, k8_shape, bs=bs)
    except KernelSpecError as e:
        emit("kernel_tile_divisibility", f"{arch}:kv_decode", str(e))
        return
    try:
        sp.check_budget()
    except KernelSpecError as e:
        emit("kernel_vmem_budget", f"{arch}:kv_decode", str(e))


def _sweep_fakequant(arch: str, path: str, K: int, N: int,
                     emit: Callable[[str, str, str], None]) -> None:
    from ...kernels.spec import describe_fakequant, fakequant_tiles

    bk, bn = fakequant_tiles(K, N)
    try:
        sp = describe_fakequant((K, N), (1, N), bk=bk, bn=bn)
    except KernelSpecError as e:
        emit("kernel_tile_divisibility", f"{arch}:{path}[fakequant]", str(e))
        return
    try:
        sp.check_budget()
    except KernelSpecError as e:
        emit("kernel_vmem_budget", f"{arch}:{path}[fakequant]", str(e))


def audit_arch(arch: str, *, bits: int = 4, reduced: bool = False
               ) -> list[Violation]:
    """Sweep one registered config's serving + calibration launches."""
    from ...deploy.pack import quantize_tree
    from ...models import get_model

    out: list[Violation] = []

    def emit(rule: str, subject: str, msg: str) -> None:
        out.append(Violation(rule, subject, msg))

    cfg, model = get_model(arch, reduced=reduced)
    fp = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    packed = jax.eval_shape(lambda p: quantize_tree(p, bits), fp)
    for path, K, wp_shape, qs_shape in _iter_packed(fp, packed):
        _sweep_leaf(arch, path, K, wp_shape, qs_shape, emit)
        # the AdaRound fused forward runs on the 2-D per-layer FP view
        N = wp_shape[-1]
        _sweep_fakequant(arch, path, K, N, emit)
    _sweep_kv(arch, cfg, emit)
    return out


def run_kernel_checks(archs, *, bits: int = 4, reduced: bool = False,
                      verbose: Callable[[str], None] = lambda s: None
                      ) -> list[Violation]:
    out: list[Violation] = []
    for arch in archs:
        found = audit_arch(arch, bits=bits, reduced=reduced)
        verbose(f"  {arch}: " + (f"{len(found)} violation(s)"
                                 if found else "ok"))
        out.extend(found)
    return out
