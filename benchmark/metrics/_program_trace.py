"""One reduction shared by the metrics that read what the program names from
inside (PR 25): device seconds per program run, the split of a wave-program
run at its block-decode loop, and host spans with self time.

It reads `ctx.profile` (the traced slice), `ctx.cluster.acks` and
`ctx.outcome.trace_span`, and nothing of the program:

- device plane, line "XLA Modules": one event per program run, named
  `jit_<program>(<id>)`. The program names its serving steps
  (`engine.named_program`): `jit_wave`, `jit_prefix_prefill_kv`.
- device plane, line "XLA Ops": the `while` events. A wave-program run
  holds the layer scan of its suffix prefill (a `while`) and the block
  decode loop (a `while` whose body holds the layer scan again); the
  decode loop is the outermost `while` of the run that holds another, or
  the run's last outermost `while` where none nests. Time inside it =
  scope `block_decode`; the rest of the run = `suffix_prefill` + epilogue.
- host plane: the program's own spans, `engine.*` on the engine worker's
  thread and `sched.*` on the event loop's, with their keyword stats.
  Self time of a span = its time less the spans of the same set that it
  covers on the same thread.

A program that names nothing (the parent of PR 25) has no `jit_wave` run
and no `engine.*` span: `reduced(ctx)` is then None, and every reader built
on it returns None.
"""

from __future__ import annotations

from harness import xplane

MODULES_LINE = "XLA Modules"
WAVE = "jit_wave"
PREFIX = "jit_prefix_prefill_kv"
# waiting, not work: blocked on the queue, the coalescing sleep, the
# is_ready() poll (less what nests in it), the blocking device_get, and
# the calls that enqueue device work, which block while the device's queue
# is full (on a busy device that is most of a wave's time, PERF.md §6)
WORKER_WAITS = ("engine.queue_wait", "engine.admit_hold", "engine.harvest_poll", "engine.harvest_wait",
                "engine.dispatch")
# only ever synchronous on the event loop's thread (the others cover awaits)
LOOP_SYNC = ("sched.render", "sched.delta_encode", "sched.tokenize", "sched.cache_lookup", "sched.bind_call")
# waves in flight at the two edges of the slice (five overlap, plus one)
EDGE_WAVES = 6


def _device_events(profile, line_name: str, device: int):
    for plane in profile.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == device:
            for line in plane.lines:
                if line.name == line_name:
                    yield from line.events


def module_runs(profile, device: int = 0) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, program) of every program run on one device;
    `jit_wave(1944519079386388448)` -> `jit_wave`."""
    return sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name.split("(")[0])
                  for ev in _device_events(profile, MODULES_LINE, device))


def device_ops(profile, device: int = 0) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, kind) of every "XLA Ops" event on one device,
    a wrapper before the body operation that starts with it. The kind is
    the compiler's name for the operation less its number: `%while.48 =
    ...` -> `while`."""
    return sorted(((ev.start_ns, ev.start_ns + ev.duration_ns,
                    ev.name.lstrip("%").split(" ", 1)[0].split(".")[0])
                   for ev in _device_events(profile, xplane.OPS_LINE, device)),
                  key=lambda e: (e[0], -e[1]))


def decode_loop_ns(whiles: list[tuple[float, float]]) -> float:
    """Time inside the block-decode loop among the `while` events of ONE
    wave-program run (sorted, wrapper first)."""
    outer: list[list] = []  # [start, end, holds another while]
    for a, b in whiles:
        if outer and a < outer[-1][1]:
            outer[-1][2] = True
        else:
            outer.append([a, b, False])
    if not outer:
        return 0.0
    nesting = [o for o in outer if o[2]]
    a, b, _ = nesting[-1] if nesting else outer[-1]
    return b - a


def host_spans(profile, prefixes: tuple[str, ...]) -> dict[str, list[tuple[float, float, str, dict]]]:
    """thread -> [(start_ns, end_ns, name, stats)] of the host events whose
    name starts with one of `prefixes`, by start. A thread is one line of
    a host plane; threads may share a line name, so the key is the line's
    place in its plane."""
    out: dict[str, list] = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            rows = []
            for ev in line.events:
                name = ev.name.split("#")[0]
                if name.startswith(prefixes):
                    rows.append((ev.start_ns, ev.start_ns + ev.duration_ns, name, dict(ev.stats)))
            if rows:
                out[f"{plane.name}#{i}:{line.name}"] = sorted(rows, key=lambda e: (e[0], -e[1]))
    return out


def self_times(rows: list[tuple[float, float, str, dict]]) -> list[tuple[str, float]]:
    """(name, self ns) of each span of one thread: its time less the
    spans it covers. `rows` sorted by start, the longer first."""
    out: list[list] = []
    stack: list[tuple[float, int]] = []  # (end, index in out)
    for a, b, name, _stats in rows:
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(b, stack[-1][0]) - a
        out.append([name, b - a])
        stack.append((b, len(out) - 1))
    return [(n, t) for n, t in out]


def reduced(ctx) -> dict | None:
    """All times in seconds; None where the program names nothing."""
    if "_program_trace" in ctx.__dict__:
        return ctx._program_trace
    ctx._program_trace = out = _reduce(ctx)
    return out


def _reduce(ctx) -> dict | None:
    if ctx.profile is None or ctx.outcome.trace_span is None:
        return None
    runs = module_runs(ctx.profile)
    waves = [(a, b) for a, b, name in runs if name == WAVE]
    if not waves:
        return None
    prefixes = [(a, b) for a, b, name in runs if name == PREFIX]
    ops = device_ops(ctx.profile)
    whiles = [(a, b) for a, b, kind in ops if kind == "while"]
    decode = 0.0
    wi = 0
    for a, b in waves:
        while wi < len(whiles) and whiles[wi][0] < a:
            wi += 1
        mine = []
        while wi < len(whiles) and whiles[wi][0] < b:
            mine.append(whiles[wi])
            wi += 1
        decode += decode_loop_ns(mine)
    wave_ns = sum(b - a for a, b in waves)
    prefix_ns = sum(b - a for a, b in prefixes)
    busy = xplane._union([(a, b) for a, b, _kind in ops])  # as xplane.reduce: busy_s
    other = busy
    for a, b in waves + prefixes:
        other, _ = xplane._subtract(other, a, b)
    ta, tb = ctx.outcome.trace_span
    binds = sum(1 for t in ctx.cluster.acks.values() if ta <= t < tb)

    threads = host_spans(ctx.profile, ("engine.", "sched."))
    worker = max(threads.values(), key=lambda rows: sum(1 for r in rows if r[2] == "engine.tick"),
                 default=[])
    worker = [r for r in worker if r[2].startswith("engine.")]
    submits = [r for r in worker if r[2] == "engine.submit_wave"]
    harvests = [r for r in worker if r[2] == "engine.harvest_wave"]
    loop = max(threads.values(), key=lambda rows: sum(1 for r in rows if r[2] == "sched.decision"),
               default=[])
    loop_sync = [r for r in loop if r[2] in LOOP_SYNC]
    ns = 1e-9
    return {
        "wave_runs": len(waves), "wave_s": wave_ns * ns, "decode_s": decode * ns,
        "prefix_runs": len(prefixes), "prefix_s": prefix_ns * ns,
        "other_s": sum(b - a for a, b in other) * ns,
        "busy_s": sum(b - a for a, b in busy) * ns,
        "binds": binds,
        "submits": len(submits), "harvests": len(harvests),
        # the k-th wave-program run is the k-th engine.submit_wave of the
        # slice (one FIFO device queue); counts further apart than the
        # waves in flight at the edges mean the two do not line up
        "aligned": abs(len(waves) - len(submits)) <= EDGE_WAVES,
        "wave_numbers": [int(r[3]["wave"]) for r in submits if "wave" in r[3]],
        "worker_self_s": sum(t for n, t in self_times(worker) if n not in WORKER_WAITS) * ns,
        "worker_spans": len(worker),
        "loop_self_s": sum(t for _n, t in self_times(loop_sync)) * ns,
        "loop_spans": len(loop_sync),
    }


def per_bind_ms(ctx, key: str):
    """1000 x r[key] / binds of the slice, where the wave-program runs
    line up with the program's own engine.submit_wave annotations."""
    r = reduced(ctx)
    if r is None or not r["aligned"] or not r["binds"]:
        return None
    return 1000.0 * r[key] / r["binds"]
