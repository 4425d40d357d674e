"""Routing imbalance: the busiest expert's tokens over the mean over all
routed experts, per expert layer per model call of the window's waves:
(`moe_max_load` ÷ calls) ÷ (`moe_assignments` ÷ calls ÷ `n_routed_experts`).
1 is perfectly even (model)."""

from metrics import _moe


def read(ctx):
    c = _moe.counters(ctx)
    if c is None or not c["assignments"]:
        return None
    return c["max_load"] * ctx.conf["n_routed_experts"] / c["assignments"]
