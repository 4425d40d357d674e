"""Share of the valid tokens' router picks that fell on identity experts,
over the window's waves: `moe_zero_assignments` ÷ (`moe_zero_assignments` +
`moe_ffn_assignments`), %. 256 of 768 outputs: 33.3 under a level router;
what varies the work a token needs (model). A program without the counters
reads 0 picks and the reader returns None."""

from metrics import _moe


def read(ctx):
    zero = ctx.delta(*_moe.ENGINE, "moe_zero_assignments")
    ffn = ctx.delta(*_moe.ENGINE, "moe_ffn_assignments")
    return 100.0 * zero / (zero + ffn) if zero + ffn > 0 else None
