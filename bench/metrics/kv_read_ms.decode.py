"""Device self time per decode program execution of the non-kernel
operations in the ``kv_read`` scope (the paged view's gathers and scale
casts), in ms; the ``kv_decode`` kernel is ``kv_roofline.decode``'s."""
from harness import phases


def read(ctx):
    ph = phases.of_run(ctx)
    if ph is None or not ph.has_scopes("decode_fn"):
        return None
    return 1e3 * ph.split("decode_fn")["kv_read"]
