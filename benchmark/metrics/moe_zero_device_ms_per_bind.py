"""Device time of the operations under scope `moe_zero` (the identity
experts: a token's normed stream times the summed weights of its picks
among them, and the two counts), per bind acknowledged in the traced slice
(model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "moe_zero")
