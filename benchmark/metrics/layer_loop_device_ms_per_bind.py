"""Device time of the operations whose innermost scope is `model` itself:
the layer scan's own loop inside a block-decode model call (the waits of
the compiler's weight prefetch, the per-layer slices out of the stacked
weights), beside the scopes `model` holds; per bind acknowledged in the
traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "model", own=True)
