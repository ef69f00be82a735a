"""Roofline share of the packed-matmul kernels inside the decode program:
the least time the chip needs for every layer's packed weights (and an
untied head) against the live decode rows of each call, over the time
the qmm kernels (whichever tier) took there."""
from harness import flops as F

KERNELS = ("qgemv", "qmatmul")


def read(ctx):
    calls = getattr(ctx.run, "decode_calls", None)
    t = ctx.trace.kernel_time("decode_fn", KERNELS)
    if not calls or not t:
        return None
    bits = ctx.run.q["w_bits"]
    need = sum(F.qmm_min_time(ctx.dims, len(lens), bits, ctx.peaks) for lens in calls)
    return 100.0 * need / t
