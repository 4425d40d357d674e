"""Device time of the prefix-prefill program runs (`jit_prefix_prefill_kv`
on the "XLA Modules" line), per bind acknowledged in the traced slice
(engine)."""

from metrics import _program_trace


def read(ctx):
    return _program_trace.per_bind_ms(ctx, "prefix_s")
