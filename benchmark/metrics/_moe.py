"""What the readers of the sparse-expert layer share: the window's deltas of
the counters the wave program sums on the device (`engine.stats`
`moe_assignments`, `moe_experts_hit`, `moe_layer_calls`, `moe_max_load`:
models/mla_moe.py COUNTERS), and the roofline share of a grouped-matmul
kernel. A program without the counters (a parent, another architecture)
reads zero calls: every reader then returns None and raises nothing.
"""

from __future__ import annotations

from harness import seam, xplane
from metrics import _program_trace

ENGINE = ("sched", "client", "engine")


def counters(ctx) -> dict | None:
    """Window deltas {assignments, experts_hit, layer_calls, max_load,
    waves}; None where no expert layer ran."""
    out = {name: ctx.delta(*ENGINE, f"moe_{name}")
           for name in ("assignments", "experts_hit", "layer_calls", "max_load")}
    out["waves"] = ctx.delta(*ENGINE, "waves")
    return out if out["layer_calls"] > 0 and out["waves"] > 0 else None


def kernel_roofline(ctx, kernel: str, k: int, n: int, n_weights: int, out_bytes: int):
    """The kernel's share of its roofline over the wave-program runs that
    lie whole inside the traced slice: least time ÷ device time of its
    events there. Least time of ONE wave = max(FLOPs / bf16 peak, bytes /
    HBM peak) of the rows and touched experts a wave has (the window's
    counters ÷ waves; every wave of a cell is the same size), with the cost
    function of arch/mla_moe.py; taking the max over a wave's sums, not
    call by call, can only read lower."""
    c = counters(ctx)
    if c is None or ctx.profile is None:
        return None
    runs = [(a, b) for a, b, name in _program_trace.module_runs(ctx.profile) if name == _program_trace.WAVE]
    events = sorted((ev.start_ns, ev.duration_ns)
                    for ev in _program_trace._device_events(ctx.profile, xplane.OPS_LINE, 0)
                    if ev.name.lstrip("%").startswith(kernel))
    if not runs or not events:
        return None
    first, last = events[0][0], events[-1][0] + events[-1][1]
    whole = [(a, b) for a, b in runs if a >= first and b <= last] or runs
    spent = sum(d for s, d in events if any(a <= s < b for a, b in whole)) * 1e-9
    flops, moved = seam.program(ctx.conf).grouped_kernel_cost(
        c["assignments"] / c["waves"], c["experts_hit"] / c["waves"], k, n, n_weights, out_bytes)
    least = len(whole) * max(flops / ctx.peaks["bf16_flops"], moved / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent if spent else None
