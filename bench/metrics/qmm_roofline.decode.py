"""Roofline share of the packed-matmul kernels inside the decode program:
the least time the chip needs for every layer's packed weights (and an
untied head) against the live decode rows of each call, over the time
the qmm kernels (whichever tier) took there."""
from harness import flops as F

KERNELS = ("qgemv", "qmatmul", "qmatmul_grouped")


def read(ctx):
    calls = getattr(ctx.run, "decode_calls", None)
    t = ctx.trace.kernel_time("decode_fn", KERNELS)
    if not calls or not t:
        return None
    bits = ctx.run.q["w_bits"]
    need = F.total(ctx.arch.decode_qmm_s(ctx.dims, note, bits, ctx.peaks)
                   for note in calls)
    return None if need is None else 100.0 * need / t
