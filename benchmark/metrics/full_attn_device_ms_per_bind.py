"""Device time of the operations under scope `full_attn` (the gated softmax
attention of every fourth layer: W_q with its gate, W_k, W_v, the norms and
partial rotary, scores against prefix, suffix, generated and block caches,
sigmoid gate, W_o; models/gdn_moe.py `full_attention`), per bind
acknowledged in the traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "full_attn")
