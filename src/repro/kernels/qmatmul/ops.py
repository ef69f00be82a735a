"""Public jit'd wrapper + shape-driven tier dispatcher for the packed
dequant-matmul.

``qmm(x, qw)`` consumes a :class:`QuantizedLinear` produced from BRECQ
output (pack_weights / from_node) and routes it to one of three
execution tiers by shape alone — callers (``QuantHook.packed_matmul``,
``launch/serve.py``, the dryrun decode cells) never pick a kernel:

  decode    M <= DECODE_M_MAX rows (a decode step's batch): the skinny
            ``qgemv`` kernel — no zero-row padding of M up to the 8/128
            sublane tile, scales applied to the partial sums.
  prefill   everything else 2-D: the tiled ``qmatmul`` GEMM.
  grouped   stacked expert nodes (packed.ndim == 3): ``qmatmul_grouped``
            over (E, K*bits/8, N), one expert grid step at a time.

On CPU each tier runs its XLA reference (the Pallas kernels are
exercised in interpret mode by tests); on TPU the Pallas kernels
compile. ``backend`` / ``QuantHook.packed_backend`` still forces a path.

Decode-shaped calls can additionally dispatch by *measurement* instead
of the M-threshold guess: an installed per-shape table of timed tier
winners (:func:`set_dispatch_table`, built by
``repro.deploy.budget.cost``) overrides the heuristic — see
:func:`select_tier` / ``REPRO_QMM_DISPATCH``.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp

from ...core.quantizer import pack_int
from ..spec import qmm_row_tile
from .kernel import qgemv, qmatmul, qmatmul_grouped
from .ref import (qgemv_ref, qmatmul_ref, qmm_grouped_dense_ref,
                  qmm_grouped_ref)

Array = jax.Array

# Largest row count served by the decode tier: one f32 sublane tile.
# Decode steps are M = batch rows; beyond 8 rows the MXU-tiled prefill
# GEMM wins anyway, so the gemv specialization stops paying.
DECODE_M_MAX = 8

# Decode-tier opt-out. On some backends the gemv specialization loses to
# the tiled GEMM even at decode shapes (BENCH_serve.json records
# decode_ratio_tier_vs_legacy < 1 on CPU); operators can force those
# shapes onto the prefill tier without a rebuild:
#   env   REPRO_QMM_DECODE_TIER=0|false|off   (read at import)
#   code  set_decode_tier(False)              (overrides the env)
_FALSY = ("0", "false", "off", "no")
_DECODE_TIER_FORCED: bool | None = None  # set_decode_tier override


def _env_decode_tier() -> bool:
    return os.environ.get("REPRO_QMM_DECODE_TIER", "1").lower() not in _FALSY


def decode_tier_enabled() -> bool:
    """Whether decode-shaped matmuls may use the gemv tier."""
    if _DECODE_TIER_FORCED is not None:
        return _DECODE_TIER_FORCED
    return _env_decode_tier()


def set_decode_tier(enabled: bool | None) -> None:
    """Force the decode tier on/off (``None`` returns control to the
    ``REPRO_QMM_DECODE_TIER`` env var). Takes effect at the next trace —
    already-compiled programs keep the tier they were traced with."""
    global _DECODE_TIER_FORCED
    _DECODE_TIER_FORCED = enabled


# Measured dispatch. The M <= DECODE_M_MAX heuristic guesses which tier
# wins at decode shapes; BENCH_serve.json records it guessing wrong on
# CPU (decode_ratio_tier_vs_legacy < 1). A measured dispatch table —
# (K, N, container_bits) -> winning tier, produced by timing each
# eligible tier at the artifact's actual shapes
# (repro.deploy.budget.cost.measure_cost_table, installed via
# install_dispatch) — overrides the guess for the shapes it covers:
#   env   REPRO_QMM_DISPATCH=heuristic|measured  (forces the mode)
#   auto  (default): measured iff a table is installed
_DISPATCH_TABLE: dict[tuple[int, int, int], str] | None = None


def set_dispatch_table(table: dict[tuple[int, int, int], str] | None) -> None:
    """Install (or clear) the measured dispatch table. Takes effect at
    the next trace, like :func:`set_decode_tier`."""
    global _DISPATCH_TABLE
    _DISPATCH_TABLE = table


def dispatch_mode() -> str:
    """Resolved dispatch mode: the ``REPRO_QMM_DISPATCH`` env override
    when set, else ``'measured'`` iff a table is installed."""
    mode = os.environ.get("REPRO_QMM_DISPATCH", "auto").lower()
    if mode in ("heuristic", "measured"):
        return mode
    return "measured" if _DISPATCH_TABLE else "heuristic"

# Trace-time tier counters (reset with ``reset_tier_counts``): each jit
# trace that routes through qmm bumps its tier once, so tests and the
# serve benchmark can assert which kernels a program actually compiled
# against without instrumenting jaxprs.
TIER_COUNTS = {"decode": 0, "prefill": 0, "grouped": 0}


def reset_tier_counts() -> None:
    for k in TIER_COUNTS:
        TIER_COUNTS[k] = 0


class PackedNodeError(TypeError):
    """A params node does not have the packed layout qmm consumes."""


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedLinear:
    """Deployment weight format: packed codes + per-group scales.

    2-D (a single linear) or stacked 3-D (an expert group):

      packed  (K * bits/8, N) int8        or (E, K * bits/8, N)
      scales  (K/G, N) f32                or (E, K/G, N)
    """

    packed: Array
    scales: Array
    bits: int
    k: int  # original reduction dim

    def tree_flatten(self):
        return (self.packed, self.scales), (self.bits, self.k)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], leaves[1], *aux)


def pack_weights(codes: Array, scales, bits: int) -> QuantizedLinear:
    """codes: (K, N) int8 in [-2^{b-1}, 2^{b-1}-1]; scales broadcastable."""
    k, n = codes.shape
    scales = jnp.asarray(scales, jnp.float32).reshape(-1, n)
    return QuantizedLinear(pack_int(codes, bits), scales, bits, k)


def from_node(node, k: int, path: str | None = None) -> QuantizedLinear:
    """View a packed params node (`repro.deploy` format) as a
    :class:`QuantizedLinear`. ``k`` is the original reduction dim;
    container bits are inferred from the packed row count. 3-D nodes
    (stacked experts) route to the grouped tier; ``path`` names the
    offending node in errors."""
    from ...deploy.pack import code_layout

    wp, scales = node["w"], node["qscale"]
    where = f" at {path!r}" if path else ""
    if wp.ndim not in (2, 3):
        raise PackedNodeError(
            f"packed node{where}: codes must be 2-D (K*bits/8, N) or "
            f"stacked 3-D (E, K*bits/8, N), got shape {wp.shape}")
    if scales.ndim != wp.ndim:
        raise PackedNodeError(
            f"packed node{where}: qscale rank {scales.ndim} does not match "
            f"codes rank {wp.ndim} (shapes {scales.shape} vs {wp.shape})")
    try:
        bits, _ = code_layout(wp, k)
    except ValueError as e:
        raise PackedNodeError(f"packed node{where}: {e}") from None
    return QuantizedLinear(wp, scales, bits, k)


def select_tier(m: int, qw: QuantizedLinear) -> str:
    """Execution tier for ``m`` activation rows against ``qw`` — the one
    dispatch predicate, shared by :func:`qmm` and its tests.

    Decode-shaped 2-D matmuls (``m <= DECODE_M_MAX``) consult the
    measured dispatch table when the mode resolves to ``'measured'``
    (:func:`dispatch_mode`); shapes the table does not cover — and the
    heuristic mode — fall back to the gemv guess. The decode-tier
    opt-out (:func:`set_decode_tier` / ``REPRO_QMM_DECODE_TIER``)
    still wins over everything."""
    if qw.packed.ndim == 3:
        return "grouped"
    if m > DECODE_M_MAX or not decode_tier_enabled():
        return "prefill"
    if _DISPATCH_TABLE is not None and dispatch_mode() == "measured":
        tier = _DISPATCH_TABLE.get((qw.k, qw.packed.shape[-1], qw.bits))
        if tier is not None:
            return tier
    return "decode"


def _pad_cols(qw: QuantizedLinear, bn: int) -> tuple[QuantizedLinear, int]:
    """Zero-pad ragged N up to a multiple of ``bn`` (padded scales are
    zero, so the extra columns cost nothing numerically and are sliced
    off after the kernel)."""
    n = qw.packed.shape[-1]
    pad = (-n) % bn
    if not pad:
        return qw, n
    widths = [(0, 0)] * (qw.packed.ndim - 1) + [(0, pad)]
    return dataclasses.replace(
        qw, packed=jnp.pad(qw.packed, widths),
        scales=jnp.pad(qw.scales, widths)), n


def _qmm_2d(x2: Array, qw: QuantizedLinear, backend: str, tier: str) -> Array:
    if backend == "xla":
        ref = qgemv_ref if tier == "decode" else qmatmul_ref
        return ref(x2, qw.packed, qw.scales, qw.bits)
    interpret = jax.default_backend() != "tpu"
    n = qw.packed.shape[-1]
    bn = 128 if n >= 128 else n
    qw, n = _pad_cols(qw, bn)
    if tier == "decode":
        out = qgemv(x2, qw.packed, qw.scales, bits=qw.bits, bn=bn,
                    interpret=interpret)
    else:
        m = x2.shape[0]
        bm = qmm_row_tile(m, qw.k, qw.scales.shape[0], bits=qw.bits, bn=bn,
                          x_bytes=x2.dtype.itemsize)
        pad = (-m) % bm
        if pad:
            x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        out = qmatmul(x2, qw.packed, qw.scales, bits=qw.bits, bm=bm, bn=bn,
                      interpret=interpret)
        if pad:
            out = out[:m]
    return out[:, :n] if out.shape[-1] != n else out


def _qmm_grouped(x: Array, qw: QuantizedLinear, backend: str) -> Array:
    """x (..., E, C, K) @ stacked qw (E, K*bits/8, N) -> (..., E, C, N)."""
    if x.ndim < 3:
        raise PackedNodeError(
            f"grouped qmm: stacked codes {qw.packed.shape} need (..., E, C, "
            f"K) activations, got rank-{x.ndim} {x.shape}")
    e, c, k = x.shape[-3], x.shape[-2], x.shape[-1]
    if e != qw.packed.shape[0] or k != qw.k:
        raise PackedNodeError(
            f"grouped qmm: activations (..., E={e}, C={c}, K={k}) do not "
            f"match stacked codes {qw.packed.shape} (E, K*bits/8, N)")
    lead = x.shape[:-3]
    # (..., E, C, K) -> (E, B'*C, K): experts become the leading grid dim
    xg = jnp.moveaxis(x.reshape(-1, e, c, k), 1, 0).reshape(e, -1, k)
    if backend == "xla":
        # decode rows: scan over E (one expert's (K, N) resident at a
        # time); prefill rows: batched einsum (dequant transient is a
        # good trade against serializing E contractions)
        ref = (qmm_grouped_ref if xg.shape[1] <= DECODE_M_MAX
               else qmm_grouped_dense_ref)
        out = ref(xg, qw.packed, qw.scales, qw.bits)
    else:
        m = xg.shape[1]
        n = qw.packed.shape[-1]
        bn = 128 if n >= 128 else n
        bm = m if m <= DECODE_M_MAX else qmm_row_tile(
            m, k, qw.scales.shape[1], bits=qw.bits, bn=bn,
            x_bytes=xg.dtype.itemsize)
        pad = (-m) % bm
        if pad:
            xg = jnp.pad(xg, ((0, 0), (0, pad), (0, 0)))
        qw, n = _pad_cols(qw, bn)
        out = qmatmul_grouped(xg, qw.packed, qw.scales, bits=qw.bits, bm=bm,
                              bn=bn, interpret=jax.default_backend() != "tpu")
        out = out[:, :m, :n]
    nn = out.shape[-1]
    return jnp.moveaxis(out.reshape(e, -1, c, nn), 0, 1).reshape(*lead, e, c, nn)


def qmm(x: Array, qw: QuantizedLinear, *, backend: str = "auto") -> Array:
    """Packed dequant-matmul: ``x @ dequant(qw)``, tier picked by shape.

    Args:
      x: activations, any float dtype. For a 2-D ``qw``: shape (..., K);
        leading dims are flattened to M rows for the kernel and restored
        on return. For a stacked 3-D ``qw``: shape (..., E, C, K), with
        the expert axis aligned to the codes' leading axis.
      qw: packed weight from :func:`pack_weights` / :func:`from_node` —
        int8 container codes (2/4/8-bit, ``K * bits/8`` rows) plus
        per-(group, out-channel) f32 scales.
      backend: ``'auto'`` (Pallas on TPU, XLA reference elsewhere),
        ``'pallas'`` (interpret mode off-TPU), or ``'xla'``.

    Returns:
      f32 output of shape (..., N) / (..., E, C, N).

    Tier selection (:func:`select_tier`): M <= ``DECODE_M_MAX`` rows run
    the ``qgemv`` decode kernel at the true row count; larger M runs the
    tiled prefill GEMM with ragged M zero-padded up to the 8/128 sublane
    tile; 3-D stacked nodes run the grouped expert kernel. Ragged N is
    zero-padded (zero scales) up to the lane tile and sliced back.
    """
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if qw.packed.ndim == 3:
        TIER_COUNTS["grouped"] += 1
        return _qmm_grouped(x, qw, backend)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, qw.k)
    tier = select_tier(x2.shape[0], qw)
    TIER_COUNTS[tier] += 1
    return _qmm_2d(x2, qw, backend, tier).reshape(*lead, -1)
