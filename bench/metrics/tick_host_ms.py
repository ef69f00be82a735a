"""Host time per engine tick, in ms: each ``serve.tick`` span less the
parts its ``serve.*.fetch`` spans cover (the host waiting for the device),
averaged over the ticks in the traced window."""
from harness import phases


def read(ctx):
    ph = phases.of_run(ctx)
    t = ph.tick_host_s() if ph else None
    return None if t is None else 1e3 * t
