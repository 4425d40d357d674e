"""Device time of the wave-program runs outside their block-decode loop
(= scope `suffix_prefill` and the epilogue), per bind acknowledged in the
traced slice (model)."""

from metrics import _program_trace


def read(ctx):
    r = _program_trace.reduced(ctx)
    if r is None or not r["aligned"] or not r["binds"]:
        return None
    return 1000.0 * (r["wave_s"] - r["decode_s"]) / r["binds"]
