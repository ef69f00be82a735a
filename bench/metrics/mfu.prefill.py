"""Model FLOPs of the prefill chunks' real prompt rows (each attending to
its own prefix) over the prefill program's device time, as a share of
the bf16 peak."""
from harness import flops as F


def read(ctx):
    calls = getattr(ctx.run, "chunk_calls", None)
    runs = ctx.trace.module_runs("chunk_fn")
    if not calls or not runs:
        return None
    work = F.total(ctx.arch.chunk_flops(ctx.dims, note) for note in calls)
    if work is None:
        return None
    return 100.0 * work / sum(r.dur for r in runs) / ctx.peaks["bf16_flops_per_s"]
