"""Device time per execution of the engine's prefill-chunk program, in ms."""


def read(ctx):
    runs = ctx.trace.module_runs("chunk_fn")
    if not runs:
        return None
    return 1e3 * sum(r.dur for r in runs) / len(runs)
