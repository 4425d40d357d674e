"""Of the prefix query positions block decode would compute at its block's
full width, the share it computed: `block_prefix_positions` (each model
call's rows x the width its prefix part ran at, the narrowest rung that held
every row's run) ÷ `block_prefix_positions_full` (rows x the block width),
over the window's waves (ops/attention.py BLOCK_PREFIX_COUNTERS), % (model).
Lower where the narrowing engages more; 100 where every call took the full
width. A program that does not count them (a parent, a family whose block
decode attends the prefix at every position) reads None, not 0."""

from metrics import _moe


def read(ctx):
    full = ctx.delta(*_moe.ENGINE, "block_prefix_positions_full")
    if full <= 0:
        return None
    return 100.0 * ctx.delta(*_moe.ENGINE, "block_prefix_positions") / full
