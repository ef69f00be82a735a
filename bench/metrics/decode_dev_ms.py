"""Device time per execution of the engine's decode program, in ms."""


def read(ctx):
    runs = ctx.trace.module_runs("decode_fn")
    if not runs:
        return None
    return 1e3 * sum(r.dur for r in runs) / len(runs)
