"""Mean device duration of one wave-program run (`jit_wave` on the "XLA
Modules" line): what a wave costs the device, beside wave_ms, which is host
clock submit -> harvest over five overlapping waves (engine)."""

from metrics import _program_trace


def read(ctx):
    r = _program_trace.reduced(ctx)
    if r is None or not r["aligned"]:
        return None
    return 1000.0 * r["wave_s"] / r["wave_runs"]
