"""`ssd_chunk_scan`'s share of its roofline (ops/ssd_scan.py: a Mamba-2
layer's chunked recurrence, the state read and written once a row a call),
from the device trace and the wave's own counters: over the kernel's events
inside the wave-program runs of the traced slice, the sum of max(FLOPs /
bf16 peak, bytes / HBM peak) of each call (arch/mamba2_hybrid.py
`ssd_kernel_cost`, rows and chunks from the event's result shape, Y as
the kernel leaves it [rows, heads, chunks, head width, chunk], the valid
positions as the window's `state_tokens_valid` share of
`state_tokens_computed`), over the sum of their device time (kernels). None on a program without the kernel or the
counters (a parent, another architecture)."""

import re

from harness import seam, xplane
from metrics import _moe, _program_trace

KERNEL = "ssd_chunk_scan"
_SHAPE = re.compile(r"f32\[(\d+),(\d+),(\d+),(\d+),(\d+)\]")


def read(ctx):
    computed = ctx.delta(*_moe.ENGINE, "state_tokens_computed")
    if computed <= 0 or ctx.profile is None:
        return None
    share = ctx.delta(*_moe.ENGINE, "state_tokens_valid") / computed
    runs = [(a, b) for a, b, name in _program_trace.module_runs(ctx.profile) if name == _program_trace.WAVE]
    arch = seam.program(ctx.conf)
    least = spent = 0.0
    for ev in _program_trace._device_events(ctx.profile, xplane.OPS_LINE, 0):
        if not ev.name.lstrip("%").startswith(KERNEL) or not any(a <= ev.start_ns < b for a, b in runs):
            continue
        m = _SHAPE.search(xplane.short_name(ev.name))
        if m is None:
            continue
        rows, _heads, chunks, _width, chunk = (int(x) for x in m.groups())
        flops, moved = arch.ssd_kernel_cost(rows, share * chunks * chunk, chunk, ctx.conf)
        least += max(flops / ctx.peaks["bf16_flops"], moved / ctx.peaks["hbm_bytes_per_s"])
        spent += ev.duration_ns * 1e-9
    return 100.0 * least / spent if spent else None
