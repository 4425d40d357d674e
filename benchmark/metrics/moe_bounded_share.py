"""Share of the routed layer's calls, over the window's waves, whose held
assignments all lay within the layer's bound, so that it gathered,
multiplied and combined those rows alone and not every token x pick row
(models/mla_moe.py `held_bound`): `moe_bounded_calls` ÷ `moe_layer_calls`,
% (model). A program that does not count `moe_bounded_calls` (a parent of
the PR that brought the bound) reads None, not 0."""

from metrics import _moe


def read(ctx):
    stats = ctx.outcome.after
    for key in _moe.ENGINE:
        stats = stats.get(key, {}) if isinstance(stats, dict) else {}
    calls = ctx.delta(*_moe.ENGINE, "moe_layer_calls")
    if "moe_bounded_calls" not in stats or calls <= 0:
        return None
    return 100.0 * ctx.delta(*_moe.ENGINE, "moe_bounded_calls") / calls
