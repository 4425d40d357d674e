"""Device busy time outside wave and prefix-prefill program runs (eager
operations, the LCP seed's programs, transfers), per bind acknowledged in
the traced slice (device). With the three other per-bind device metrics it
sums to device busy time per bind."""

from metrics import _program_trace


def read(ctx):
    return _program_trace.per_bind_ms(ctx, "other_s")
