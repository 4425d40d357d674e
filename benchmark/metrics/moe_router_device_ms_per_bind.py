"""Device time around the experts of an expert layer: scopes `moe_router`
(float32 logits, sigmoid, top-k, weights), `moe_dispatch` (sort by expert,
group sizes, gather) and `moe_combine` (un-sort, weighted sum), per bind
acknowledged in the traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    parts = [_scope_trace.per_bind_ms(ctx, s) for s in ("moe_router", "moe_dispatch", "moe_combine")]
    return None if any(p is None for p in parts) else sum(parts)
