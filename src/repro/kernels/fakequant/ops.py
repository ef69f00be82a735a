"""Public wrapper for the fused AdaRound forward."""
from __future__ import annotations

import jax

from ...core.quantizer import QConfig, QState
from ..spec import KernelSpecError, fakequant_tiles
from .kernel import fakequant
from .ref import fakequant_ref


def adaround_forward(w: jax.Array, v: jax.Array, st: QState, cfg: QConfig,
                     *, hard: bool = False, backend: str = "auto") -> jax.Array:
    """Kernel-backed equivalent of ``core.adaround.soft_quant`` /
    ``hard_quant`` for 2-D per-channel weights (symmetric, no grouping).

    Args:
      w: FP weight of shape (K, N).
      v: AdaRound rounding logits, same shape as ``w``.
      st: quantizer state; ``st.scale`` must broadcast to (1, N) (one
        scale per output channel).
      cfg: static quantizer config supplying the clip range
        ``[qmin, qmax]``; must be symmetric with ``group_size=None``.
      hard: ``False`` — soft (differentiable) rounding with the rectified
        sigmoid of ``v``; ``True`` — hardened rounding ``(v >= 0)``.
      backend: ``'auto'`` (Pallas on TPU, XLA reference elsewhere),
        ``'pallas'``, or ``'xla'``.

    Returns:
      Fake-quantized weight, shape (K, N), f32.

    Raises:
      KernelSpecError: for weight ranks or quantizer configs the fused
        kernel does not cover (grouped or asymmetric quantization) —
        callers fall back to ``core.adaround`` for those.
    """
    if w.ndim != 2:
        raise KernelSpecError(
            f"adaround_forward: weights must be 2-D (K, N), got shape "
            f"{tuple(w.shape)}")
    if cfg.group_size is not None or not cfg.symmetric:
        raise KernelSpecError(
            f"adaround_forward: only symmetric per-channel quantization is "
            f"fused (group_size=None, symmetric=True); got unsupported "
            f"config group_size={cfg.group_size}, symmetric={cfg.symmetric}")
    scale = st.scale.reshape(-1, w.shape[1])
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "xla":
        return fakequant_ref(w, v, scale, cfg.qmin, cfg.qmax, hard)
    interpret = jax.default_backend() != "tpu"
    bk, bn = fakequant_tiles(*w.shape)
    return fakequant(w, v, scale, qmin=cfg.qmin, qmax=cfg.qmax, hard=hard,
                     bk=bk, bn=bn, interpret=interpret)
