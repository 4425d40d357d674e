"""Reduction of a JAX profiler trace (.xplane.pb) to the numbers the benchmark
reports: device busy and idle time, idle gaps by what the host was doing,
time per device operation, kernel time, collective time.

Only jax is needed to read the file (jax.profiler.ProfileData). Device
planes are the ones named "/device:TPU:<n>"; on each, the line "XLA Ops"
holds one event per executed HLO operation (a fusion, a copy, a Pallas
kernel under the name Mosaic gave it) with its start and duration in
nanoseconds. Host threads are lines of the "/host:CPU" plane; the spans the
benchmark wraps around the calls into each layer appear there under the
names in `system.HOST_SPANS`.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
# the most specific span wins when several cover an idle gap's start
SPAN_PRIORITY = ("submit_wave", "harvest_wave", "prefix_prefill", "bind", "snapshot", "decide")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


_HLO = re.compile(r"^%?([^\s=]+) = \(?(\w+\[[\d,]*\])?")
WRAPPERS = ("while", "conditional", "call")  # their bodies' operations are events of their own


def short_name(name: str) -> str:
    """An event's name is the operation's whole HLO text; kept: the name
    the compiler gave it and its (first) result shape,
    `flash_prefix_attention_parts.16 f32[8,384,128]`."""
    m = _HLO.match(name)
    if m is None:
        return name[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def _subtract(parts: list[tuple[float, float]], a: float, b: float) -> tuple[list[tuple[float, float]], float]:
    """Remove [a, b) from the disjoint intervals `parts`; returns what is
    left and how much was removed."""
    left, removed = [], 0.0
    for x, y in parts:
        lo, hi = max(x, a), min(y, b)
        if lo >= hi:
            left.append((x, y))
            continue
        removed += hi - lo
        if x < lo:
            left.append((x, lo))
        if hi < y:
            left.append((hi, y))
    return left, removed


def reduce(profile, span_names=SPAN_PRIORITY, top: int = 10) -> dict:
    """All times in seconds. `busy_s` is the union of operation intervals
    on a device, averaged over the devices; `window_s` runs from the first
    to the last event seen on any device or span line."""
    devices: dict[int, list[tuple[float, float, str]]] = {}
    spans: dict[str, list[tuple[float, float]]] = {n: [] for n in span_names}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = devices.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        spans[ev.name].append((ev.start_ns, ev.start_ns + ev.duration_ns))
    if not devices:
        raise ValueError("the trace holds no device plane with an 'XLA Ops' line")
    starts = [op[0] for ops in devices.values() for op in ops]
    ends = [op[1] for ops in devices.values() for op in ops]
    for ivs in spans.values():
        starts += [a for a, _ in ivs]
        ends += [b for _, b in ivs]
    w0, w1 = min(starts), max(ends)
    busy, op_time, gaps_by = [], {}, {}
    collective_ns = 0.0
    for dev, ops in sorted(devices.items()):
        merged = _union([(a, b) for a, b, _ in ops])
        busy.append(sum(b - a for a, b in merged))
        for a, b, name in ops:
            short = short_name(name)
            kind = short.split(" ")[0].split(".")[0]
            if kind in WRAPPERS:
                continue
            op_time[short] = op_time.get(short, 0.0) + (b - a)
            if kind.removesuffix("-start").removesuffix("-done") in COLLECTIVES:
                collective_ns += b - a
        if dev != min(devices):
            continue  # gaps are attributed on the first device; the others run the same program
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for ga, gb in zip(edges[0::2], edges[1::2]):
            if gb <= ga:
                continue
            # by overlap, the most specific span first (the host's and the
            # device's clocks differ by about a millisecond, so a gap's
            # first instant alone would miss the span that caused it)
            rest = [(ga, gb)]
            for n in span_names:
                for a, b in spans[n]:
                    if b <= ga or a >= gb:
                        continue
                    rest, took = _subtract(rest, a, b)
                    if took:
                        gaps_by[n] = gaps_by.get(n, 0.0) + took
            left = sum(y - x for x, y in rest)
            if left:
                gaps_by["no_annotation"] = gaps_by.get("no_annotation", 0.0) + left
    n_dev = len(devices)
    ns = 1e-9
    ranked_ops = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {
        "devices": n_dev,
        "window_s": (w1 - w0) * ns,
        "busy_s": sum(busy) / n_dev * ns,
        "collective_s": collective_ns / n_dev * ns,
        "op_seconds": {k: v / n_dev * ns for k, v in ranked_ops},
        "device_ops": [[k, v / n_dev * ns] for k, v in ranked_ops[:top]],
        "idle_gaps": [[k, v * ns] for k, v in sorted(gaps_by.items(), key=lambda kv: -kv[1])[:top]],
    }


def kernel_events(profile, prefix: str) -> list[tuple[float, str]]:
    """(seconds, short name) of every device operation whose name starts
    with `prefix`, on the first device."""
    out = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) != 0:
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                if ev.name.lstrip("%").startswith(prefix):
                    out.append((ev.duration_ns * 1e-9, short_name(ev.name)))
    return out
