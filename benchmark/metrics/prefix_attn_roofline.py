"""The flash-prefix kernel's share of its roofline, from the device trace:
sum over the kernel's events of max(FLOPs / bf16 peak, bytes / HBM peak),
over the sum of their device time. The query rows of a call are read from
the event's own result shape (in its name); the prefix length is the mean over the traced
slice's waves. For these shapes the byte bound holds (K and V of ~8k tokens
against a few hundred query rows)."""

import re

from harness import flops

KERNEL = "flash_prefix_attention_parts"
_SHAPE = re.compile(r"f32\[(\d+),(\d+),(\d+)\]")


def read(ctx):
    if ctx.profile is None:
        return None
    from harness import xplane

    events = xplane.kernel_events(ctx.profile, KERNEL)
    waves = ctx.trace_waves or ctx.waves
    if not events or not waves:
        return None
    plen = sum(len(w["prefix_ids"]) for w in waves) / len(waves)
    least = spent = 0.0
    for seconds, name in events:
        m = _SHAPE.search(name)
        if m is None:
            continue
        n_kv, rows, hd = (int(x) for x in m.groups())
        f, b = flops.prefix_kernel_cost(n_kv, rows, hd, int(plen))
        least += max(f / ctx.peaks["bf16_flops"], b / ctx.peaks["hbm_bytes_per_s"])
        spent += seconds
    return 100.0 * least / spent if spent else None
