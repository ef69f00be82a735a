"""Roofline share of the packed-matmul kernels inside the prefill-chunk
program: the least time for every packed weight against the chunk's real
prompt rows, over the time the qmm kernels took there."""
from harness import flops as F

KERNELS = ("qgemv", "qmatmul", "qmatmul_grouped")


def read(ctx):
    calls = getattr(ctx.run, "chunk_calls", None)
    t = ctx.trace.kernel_time("chunk_fn", KERNELS)
    if not calls or not t:
        return None
    bits = ctx.run.q["w_bits"]
    need = F.total(ctx.arch.chunk_qmm_s(ctx.dims, note, bits, ctx.peaks)
                   for note in calls)
    return None if need is None else 100.0 * need / t
