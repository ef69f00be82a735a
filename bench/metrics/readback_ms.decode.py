"""Time from the end of each decode program execution to the end of the
``serve.decode.fetch`` span it ended in, in ms: the copy of the logits to
the host and the host's wake-up. None if a fetch holds no execution end."""
from harness import phases


def read(ctx):
    ph = phases.of_run(ctx)
    t = ph.readback_s("decode_fn", "serve.decode.fetch") if ph else None
    return None if t is None else 1e3 * t
