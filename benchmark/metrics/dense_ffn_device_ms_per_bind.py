"""Device time of the operations under scope `dense_ffn` (the two dense
SwiGLU feed-forwards of a shortcut-connected double layer,
models/mla_scmoe.py), in every program of the slice, per bind acknowledged
in the traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "dense_ffn")
