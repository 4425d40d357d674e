"""Device time of the operations under scope `moe_shared` (the shared
expert's SwiGLU, which every token meets), per bind acknowledged in the
traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "moe_shared")
