"""Device time of the operations under scope `mlp` (the feed-forward of
every model call: suffix prefill, block decode, prefix prefill), per bind
acknowledged in the traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "mlp")
