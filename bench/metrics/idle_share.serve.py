"""Share of the harness's ``engine.step`` spans in which no operation ran
on the device; time spent waiting for arrivals is outside those spans."""


def read(ctx):
    idle, total = ctx.trace.idle_within("bench.step")
    if total <= 0:
        return None
    return 100.0 * idle / total
