"""Share of the picks on feed-forward experts that fell on experts HELD
HERE, over the window's waves: `moe_assignments` ÷ `moe_ffn_assignments`, %.
16 of 512: 3.1 under a level router; the measured counterpart of
arch/mla_scmoe.py `held_picks_per_token` (model). A program without the
counters reads 0 picks and the reader returns None."""

from metrics import _moe


def read(ctx):
    ffn = ctx.delta(*_moe.ENGINE, "moe_ffn_assignments")
    return 100.0 * ctx.delta(*_moe.ENGINE, "moe_assignments") / ffn if ffn > 0 else None
