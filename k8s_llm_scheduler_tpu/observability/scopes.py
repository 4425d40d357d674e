"""Device time by named scope, by kernel and by program, from a profiler
trace (`.xplane.pb`, what `observability/trace.py` `device_trace` or any
`jax.profiler` capture writes under `<dir>/plugins/profile/<time>/`).

The program names its device work from inside: `jax.named_scope` on the
jitted steps (engine/engine.py `_wave_impl`: `suffix_prefill`,
`block_decode`, `sample_expand`, `model`; models/llama.py: `embed`, `attn`,
`mlp`, `kv_writeback`, `lm_head`; `prefix_prefill`, `lcp_seed`), a `name=`
on every Pallas kernel, a program name on every serving `jax.jit`
(`engine.named_program`). In the trace those are, per device plane:

- line "XLA Modules": one event per program run, named
  `jit_<program>(<program id>)`;
- line "XLA Ops": one event per executed HLO operation. The event's name is
  the HLO text; the scope path lives in the plane's `event_metadata` for
  that operation, stat `tf_op` (`jit(wave)/jit(main)/block_decode/while/
  body/model/mlp/dot_general:`), beside `program_id` and `hlo_category`.
  `jax.profiler.ProfileData` does not surface `event_metadata`, so the
  file is read here as the plain protobuf it is (a wire-format reader of
  the few fields needed; no TensorFlow import);
- a `while` / `conditional` / `call` event spans its body, whose
  operations are events of their own: a wrapper's time is never summed,
  it is what an operation WITHOUT a scope of its own (a Mosaic kernel: the
  compiler keeps no `op_name` on a `tpu_custom_call`) inherits its scope
  from, by containment in time.

`reduce_scopes(path)` is the reduction; `tools/trace_scopes.py` prints it.
A persistent compile cache written before the scopes existed serves
executables without them (PERF.md §3): when no operation of a `wave`
program run carries `block_decode` the reduction says `measured: False`
and the tool prints "not measured", never "100% unscoped".
"""

from __future__ import annotations

import bisect
import re
import struct
from typing import Any, Iterator

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WRAPPERS = ("while", "conditional", "call")
WAVE_PROGRAM = "wave"
DECODE_SCOPE = "block_decode"
# path components that are structure, not names the program gave (an
# einsum leaves its equation in the path, `...d,df->...f`; a local function
# called under a scope its qualified name, `_wave_impl.<locals>.sample_expand`)
_STRUCTURE = re.compile(
    r"^(jit\(.*\)|pjit|while|body|cond|closed_call|checkpoint|remat\d*|"
    r"custom_jvp_call|custom_vjp_call|branch_\d+_fun|core_call|shard_map|.*->.*|.*<locals>.*)$"
)
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\((\d+)\))?$")
_HLO = re.compile(r"^%?([^\s=]+) = \(?(\w+\[[\d,]*\])?")


# ------------------------------------------------------------ wire format
def _varint(buf, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def _fields(buf) -> Iterator[tuple[int, int, Any]]:
    """(field number, wire type, value) of one message: a varint as int, a
    length-delimited field as a memoryview, fixed 64 / 32 as raw bytes."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value = buf[pos:pos + n]
            pos += n
        elif wire == 1:
            value = buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            value = buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf) -> tuple[int, Any]:
    """XStat -> (metadata id, value); a ref_value comes back as ("ref", id)."""
    meta, value = 0, None
    for f, _w, v in _fields(buf):
        if f == 1:
            meta = v
        elif f == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif f == 6:
            value = bytes(v)
        elif f == 7:
            value = ("ref", v)
    return meta, value


def _map_entry(buf) -> tuple[int, Any]:
    key, value = 0, b""
    for f, _w, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


class Plane:
    """One XPlane: `lines` name -> [(start_ps, duration_ps, metadata id)],
    `events` metadata id -> {"name", "stats": {stat name: value}}."""

    def __init__(self, name: str) -> None:
        self.name = name
        self.lines: dict[str, list[tuple[int, int, int]]] = {}
        self.events: dict[int, dict] = {}


def read_device_planes(path: str) -> list[Plane]:
    """The device planes of an `.xplane.pb`; host planes (the bulk of a
    capture with the Python tracer on) are skipped unparsed."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    planes = []
    for f, _w, pbuf in _fields(space):
        if f != 1:
            continue
        name = ""
        for pf, _pw, pv in _fields(pbuf):
            if pf == 2:
                name = bytes(pv).decode()
                break
        if not DEVICE_PLANE.match(name):
            continue
        plane = Plane(name)
        stat_names: dict[int, str] = {}
        raw_events: dict[int, memoryview] = {}
        raw_lines = []
        for pf, _pw, pv in _fields(pbuf):
            if pf == 3:
                raw_lines.append(pv)
            elif pf == 4:
                key, value = _map_entry(pv)
                raw_events[key] = value
            elif pf == 5:
                key, value = _map_entry(pv)
                for sf, _sw, sv in _fields(value):
                    if sf == 2:
                        stat_names[key] = bytes(sv).decode()
        for key, ebuf in raw_events.items():
            ev = {"name": "", "stats": {}}
            for ef, _ew, evv in _fields(ebuf):
                if ef == 2:
                    ev["name"] = bytes(evv).decode("utf-8", "replace")
                elif ef == 5:
                    meta, value = _stat(evv)
                    if isinstance(value, tuple):
                        value = stat_names.get(value[1], "")
                    ev["stats"][stat_names.get(meta, str(meta))] = value
            plane.events[key] = ev
        for lbuf in raw_lines:
            lname, t0_ns, events = "", 0, []
            for lf, _lw, lv in _fields(lbuf):
                if lf == 2:
                    lname = bytes(lv).decode()
                elif lf == 3:
                    t0_ns = _signed(lv)
                elif lf == 4:
                    meta = off = dur = 0
                    for xf, _xw, xv in _fields(lv):
                        if xf == 1:
                            meta = xv
                        elif xf == 2:
                            off = _signed(xv)
                        elif xf == 3:
                            dur = _signed(xv)
                    events.append((off, dur, meta))
            base = t0_ns * 1000
            # by start; a wrapper before the body operation that starts with it
            plane.lines[lname] = sorted(
                ((base + off, dur, meta) for off, dur, meta in events),
                key=lambda e: (e[0], -e[1]),
            )
        planes.append(plane)
    return planes


# --------------------------------------------------------------- reduction
def short_name(hlo_text: str) -> str:
    """`fusion.895 bf16[8,24,8192]`: the compiler's name for the operation
    and its (first) result shape, from the event's HLO text."""
    m = _HLO.match(hlo_text)
    if m is None:
        return hlo_text[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def scope_of(op_name: str) -> tuple[str, str]:
    """`jit(wave)/jit(main)/block_decode/while/body/model/mlp/dot_general:`
    -> ("block_decode/model/mlp", "dot_general"): the names the program
    gave, and the primitive. Structure (jit(..), while, body...) is
    dropped: it changes with the compiler, the names do not."""
    parts = [p for p in op_name.rstrip(":").split("/") if p]
    if not parts:
        return "", ""
    *scopes, primitive = parts
    return "/".join(p for p in scopes if not _STRUCTURE.match(p)), primitive


def program_of(module_event_name: str) -> tuple[str, int | None]:
    """`jit_wave(1944519079386388448)` -> ("wave", 1944519079386388448)."""
    m = _MODULE.match(module_event_name)
    name, pid = m.group(1), m.group(2)
    return name, int(pid) if pid else None


def _kind(short: str) -> str:
    return short.split(" ")[0].split(".")[0]


def reduce_scopes(path: str, top: int = 15) -> dict:
    """Seconds of device time by program, by scope path within each
    program, and by kernel; the operations no scope covers; and the check
    that scope `block_decode` and the time inside the wave program's
    `while` agree. First device plane only (the others run the same
    programs). All times in seconds."""
    planes = read_device_planes(path)
    if not planes:
        raise ValueError(f"{path}: no /device:TPU:<n> plane")
    plane = min(planes, key=lambda p: int(DEVICE_PLANE.match(p.name).group(1)))
    ops = plane.lines.get(OPS_LINE, [])
    if not ops:
        raise ValueError(f"{path}: {plane.name} has no '{OPS_LINE}' line")
    ps = 1e-12

    programs: dict[str, dict] = {}
    by_id: dict[int, str] = {}
    runs: list[tuple[int, int, str]] = []
    for start, dur, meta in plane.lines.get(MODULES_LINE, []):
        name, pid = program_of(plane.events[meta]["name"])
        rec = programs.setdefault(name, {"runs": 0, "seconds": 0.0})
        rec["runs"] += 1
        rec["seconds"] += dur * ps
        runs.append((start, start + dur, name))
        if pid is not None:
            by_id[pid] = name
    run_starts = [r[0] for r in runs]

    def program_at(start: int, program_id) -> str:
        """By the operation's own program id, or (a wrapper event carries
        none) by the program run it started in."""
        if program_id in by_id:
            return by_id[program_id]
        i = bisect.bisect_right(run_starts, start) - 1
        return runs[i][2] if i >= 0 and start < runs[i][1] else "?"

    # what an event's metadata says, once per distinct operation
    described: dict[int, tuple[str, bool, str, Any, bool]] = {}

    def describe(meta: int) -> tuple[str, bool, str, Any, bool]:
        """(short name, is a wrapper, own scope, program id, is a kernel)."""
        if meta not in described:
            ev = plane.events[meta]
            short = short_name(ev["name"])
            described[meta] = (
                short, _kind(short) in WRAPPERS,
                scope_of(str(ev["stats"].get("tf_op", "")))[0],
                ev["stats"].get("program_id"),
                'custom_call_target="tpu_custom_call"' in ev["name"],
            )
        return described[meta]

    # Pass 1: a wrapper event carries no scope of its own; it gets the path
    # that every scoped operation inside it shares (the decode loop:
    # `block_decode`; the layer scan inside a model call:
    # `block_decode/model`).
    shared: dict[int, list[str] | None] = {}
    open_wrappers: list[tuple[int, int]] = []  # (end_ps, index in ops)
    for i, (start, dur, meta) in enumerate(ops):
        while open_wrappers and open_wrappers[-1][0] <= start:
            open_wrappers.pop()
        _short, is_wrapper, scope, _pid, _kernel = describe(meta)
        if is_wrapper:
            open_wrappers.append((start + dur, i))
            shared[i] = None
        elif scope and open_wrappers:
            parts = scope.split("/")
            for _end, wi in open_wrappers:
                have = shared[wi]
                if have is None:
                    shared[wi] = parts
                else:
                    n = 0
                    while n < min(len(have), len(parts)) and have[n] == parts[n]:
                        n += 1
                    shared[wi] = have[:n]

    # Pass 2: seconds by scope; an operation without a scope of its own (a
    # copy the compiler put in) takes that of the wrapper it runs inside.
    scopes: dict[str, dict[str, float]] = {}
    kernels: dict[str, float] = {}
    op_rows: dict[tuple[str, str, str], float] = {}
    unscoped: dict[tuple[str, str], float] = {}
    wrappers: list[tuple[int, str]] = []  # (end_ps, scope) of open wrapper events
    decode_wrapper_s = decode_scope_s = 0.0
    busy_end = total = 0
    first = ops[0][0]
    for i, (start, dur, meta) in enumerate(ops):
        short, is_wrapper, scope, pid, is_kernel = describe(meta)
        program = program_at(start, pid)
        while wrappers and wrappers[-1][0] <= start:
            wrappers.pop()
        if is_wrapper:
            scope = "/".join(shared[i] or ())
            # outermost wrapper of scope block_decode = one wave's whole loop
            if (program == WAVE_PROGRAM and scope.split("/")[0] == DECODE_SCOPE
                    and not any(s.split("/")[0] == DECODE_SCOPE for _e, s in wrappers)):
                decode_wrapper_s += dur * ps
            wrappers.append((start + dur, scope))
            continue
        if not scope and wrappers:
            scope = wrappers[-1][1]
        seconds = dur * ps
        if start + dur > busy_end:
            total += start + dur - max(start, busy_end)
            busy_end = start + dur
        per = scopes.setdefault(program, {})
        per[scope or "(no scope)"] = per.get(scope or "(no scope)", 0.0) + seconds
        if is_kernel:
            stem = short.split(" ")[0].rsplit(".", 1)[0]
            kernels[stem] = kernels.get(stem, 0.0) + seconds
        key = (short, scope, program)
        op_rows[key] = op_rows.get(key, 0.0) + seconds
        if not scope:
            unscoped[(short, program)] = unscoped.get((short, program), 0.0) + seconds
        if program == WAVE_PROGRAM and scope.split("/")[0] == DECODE_SCOPE:
            decode_scope_s += seconds
    busy_s = total * ps
    unscoped_s = sum(unscoped.values())
    ranked = sorted(op_rows.items(), key=lambda kv: -kv[1])
    return {
        "plane": plane.name,
        "window_s": (max(s + d for s, d, _m in ops) - first) * ps,
        "busy_s": busy_s,
        # a cache from before the scopes serves executables without them
        "measured": decode_scope_s > 0.0,
        "programs": dict(sorted(programs.items(), key=lambda kv: -kv[1]["seconds"])),
        "scopes": {p: dict(sorted(s.items(), key=lambda kv: -kv[1])) for p, s in scopes.items()},
        "kernels": dict(sorted(kernels.items(), key=lambda kv: -kv[1])),
        "ops": [{"op": k[0], "scope": k[1], "program": k[2], "seconds": v} for k, v in ranked[:top]],
        "no_scope": {
            "seconds": unscoped_s,
            "share": unscoped_s / busy_s if busy_s else 0.0,
            "top": [{"op": k[0], "program": k[1], "seconds": v}
                    for k, v in sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]],
        },
        "block_decode": {"scope_s": decode_scope_s, "while_s": decode_wrapper_s},
    }
