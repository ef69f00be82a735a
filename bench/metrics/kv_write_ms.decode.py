"""Device self time per decode program execution of the operations in the
``kv_write`` scope (quantize and scatter into the paged KV pool), in ms."""
from harness import phases


def read(ctx):
    ph = phases.of_run(ctx)
    if ph is None or not ph.has_scopes("decode_fn"):
        return None
    return 1e3 * ph.split("decode_fn")["kv_write"]
