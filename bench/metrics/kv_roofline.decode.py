"""Roofline share of the int8-KV read kernel (``kv_decode``) inside the
decode program: the least time for each live stream's K and V codes and
scales up to its own length, in every layer, over the kernel's time."""
from harness import flops as F


def read(ctx):
    calls = getattr(ctx.run, "decode_calls", None)
    t = ctx.trace.kernel_time("decode_fn", ("kv_decode",))
    if not calls or not t:
        return None
    need = F.total(ctx.arch.kv_min_time(ctx.dims, note, ctx.peaks)
                   for note in calls)
    return None if need is None else 100.0 * need / t
