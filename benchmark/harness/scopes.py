"""The scope level of a profiler trace: a wire-format reader of the few
fields of an `.xplane.pb` that say under which `jax.named_scope` a device
operation ran.

On a device plane, line "XLA Ops" holds one event per executed HLO
operation; the event's name is the HLO text, and the scope path lives in
the plane's `event_metadata` for that operation, stat `tf_op`
(`jit(wave)/jit(main)/block_decode/while/body/model/mlp/dot_general:`).
`jax.profiler.ProfileData` does not surface `event_metadata`, so the file
is read here as the plain protobuf it is (no TensorFlow import). Host
planes, the bulk of a capture with the Python tracer on, are skipped
unparsed.

A copy of the reader in the program's observability/scopes.py (which
`tools/trace_scopes.py` prints): the benchmark imports nothing of the
program, and has to run against a parent that lacks that module. The
reduction built on it is metrics/_scope_trace.py.
"""

from __future__ import annotations

import re
import struct
from typing import Any, Iterator

from harness.xplane import DEVICE_PLANE

# path components that are structure, not names the program gave (an
# einsum leaves its equation in the path, `...d,df->...f`; a local function
# called under a scope its qualified name, `_wave_impl.<locals>.sample_expand`)
_STRUCTURE = re.compile(
    r"^(jit\(.*\)|pjit|while|body|cond|closed_call|checkpoint|remat\d*|"
    r"custom_jvp_call|custom_vjp_call|branch_\d+_fun|core_call|shard_map|.*->.*|.*<locals>.*)$"
)


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


# ------------------------------------------------------------------ scopes
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
