"""Distinct experts that were sent at least one token, per expert layer per
model call of the window's waves: `moe_experts_hit` ÷ `moe_layer_calls`, of
`n_routed_experts`. What a call has to read of an expert layer's weights
(model)."""

from metrics import _moe


def read(ctx):
    c = _moe.counters(ctx)
    return None if c is None else c["experts_hit"] / c["layer_calls"]
