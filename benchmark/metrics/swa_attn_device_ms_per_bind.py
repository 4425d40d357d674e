"""Device time of the operations under scope `swa_attn` (a window layer's
attention whole: W_q, W_k, W_v, the rotation, scores against the prefix's
window through the windowed kernel and against suffix, generated and block
caches through masks, W_o; three of the cell's four layers,
models/cohere2_moe.py), in every program of the slice, per bind acknowledged
in the traced slice (model). None on a program without the scope (a parent,
another architecture)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "swa_attn")
