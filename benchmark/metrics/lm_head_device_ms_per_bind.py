"""Device time of the operations under scope `lm_head` (final norm and
output head), per bind acknowledged in the traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "lm_head")
