"""`window_prefix_attention`'s share of its roofline
(ops/pallas_prefix_attention.py: queries against the WINDOW of a shared
prefix they see, key blocks wholly below it never visited), from the device
trace: over the kernel's events inside the wave-program runs of the traced
slice, the sum of max(FLOPs / bf16 peak, bytes / HBM peak) of each call, over
the sum of their device time (kernels). Counted over the keys in the window
alone (metrics/_window.py `window_kernel_cost`): the query rows of a call
from the event's own result shape, the keys each sees from the traced
slice's waves. None on a program without the kernel (a parent, another
architecture)."""

from harness import xplane
from metrics import _program_trace, _window

KERNEL = "window_prefix_attention"


def read(ctx):
    waves = ctx.trace_waves or ctx.waves
    if ctx.profile is None or not waves or "sliding_window" not in ctx.conf:
        return None
    keys = _window.prefix_keys_per_query(waves, ctx.conf["sliding_window"])
    span = _window.prefix_keys_read(waves, ctx.conf["sliding_window"])
    runs = [(a, b) for a, b, name in _program_trace.module_runs(ctx.profile) if name == _program_trace.WAVE]
    least = spent = 0.0
    for ev in _program_trace._device_events(ctx.profile, xplane.OPS_LINE, 0):
        if not ev.name.lstrip("%").startswith(KERNEL) or not any(a <= ev.start_ns < b for a, b in runs):
            continue
        m = _window.SHAPE.search(xplane.short_name(ev.name))
        if m is None:
            continue
        n_kv, rows, hd = (int(x) for x in m.groups())
        flops, moved = _window.window_kernel_cost(n_kv, rows, hd, keys, span)
        least += max(flops / ctx.peaks["bf16_flops"], moved / ctx.peaks["hbm_bytes_per_s"])
        spent += ev.duration_ns * 1e-9
    return 100.0 * least / spent if spent else None
