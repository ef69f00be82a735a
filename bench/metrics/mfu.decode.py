"""Model FLOPs of the live decode rows (each stream at its own length)
over the decode program's device time, as a share of the bf16 peak."""
from harness import flops as F


def read(ctx):
    calls = getattr(ctx.run, "decode_calls", None)
    runs = ctx.trace.module_runs("decode_fn")
    if not calls or not runs:
        return None
    work = F.total(ctx.arch.model_flops_decode(ctx.dims, note) for note in calls)
    if work is None:
        return None
    return 100.0 * work / sum(r.dur for r in runs) / ctx.peaks["bf16_flops_per_s"]
