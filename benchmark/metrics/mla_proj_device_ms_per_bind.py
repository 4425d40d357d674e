"""Device time of latent attention's projections: scopes `mla_down` (W_dq,
W_dkv and their norms) and `mla_up` (W_uq, and W_ukv written out or as its
absorbed halves), per bind acknowledged in the traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    parts = [_scope_trace.per_bind_ms(ctx, s) for s in ("mla_down", "mla_up")]
    return None if any(p is None for p in parts) else sum(parts)
