"""Device time inside the block-decode loop of the wave-program runs (the
`while` event that holds the layer scan; = scope `block_decode`), per bind
acknowledged in the traced slice (model)."""

from metrics import _program_trace


def read(ctx):
    return _program_trace.per_bind_ms(ctx, "decode_s")
