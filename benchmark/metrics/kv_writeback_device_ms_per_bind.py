"""Device time of the operations under scope `kv_writeback` (a model call's
new keys and values written into the wave's buffers), per bind acknowledged
in the traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "kv_writeback")
