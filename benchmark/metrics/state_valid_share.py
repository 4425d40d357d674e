"""Share of the positions the delta rule's scan ran over that held a token:
`state_tokens_valid` (suffix tokens + served tokens, once a model call) ÷
`state_tokens_computed` (rows x the width the scan was compiled for: 8 x
128 a suffix call, 8 x 24 a decode call, padding rows and columns
included), over the window's waves, % (model). A program that does not
count them (a parent, another architecture) reads None, not 0."""

from metrics import _moe


def read(ctx):
    computed = ctx.delta(*_moe.ENGINE, "state_tokens_computed")
    if computed <= 0:
        return None
    return 100.0 * ctx.delta(*_moe.ENGINE, "state_tokens_valid") / computed
