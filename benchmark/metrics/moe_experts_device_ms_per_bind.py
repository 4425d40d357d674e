"""Device time of the operations under scope `moe_experts` (the two grouped
matmuls of every expert layer: gate and up fused, then down), per bind
acknowledged in the traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "moe_experts")
