"""Device time of the operations under scope `attn` (QKV and output
projections, rotary, the attends and the attention kernels, of every model
call), per bind acknowledged in the traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "attn")
