"""The device a run is on, its published peaks, and what it compiled."""

from __future__ import annotations

import json
from pathlib import Path


def describe() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).with_name("peaks.json")).read_text())
    if device_kind not in table or device_kind == "comment":
        raise KeyError(f"no published peaks for device_kind {device_kind!r} in harness/peaks.json")
    return table[device_kind]


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip."""
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices())


class CompileCounter:
    """XLA programs this process built, from JAX's own monitoring events:
    `programs` counts every program new to the process, `loaded` those of
    them that the persistent cache held. Copied from
    chip_smoke.py's _CompileCounter; jax.monitoring has no unregister, so
    `active` turns it off."""

    def __init__(self) -> None:
        import jax.monitoring

        self.active = True
        self.programs = self.cache_hits = 0
        self.seconds = 0.0
        self.names: list[str] = []
        self.durations: list[float] = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_kw) -> None:
        if self.active and name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, name: str, secs: float, **kw) -> None:
        if self.active and name == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs
            self.names.append(str(kw.get("fun_name", "?")))
            self.durations.append(secs)

    def names_between(self, first: int, last: int) -> dict[str, list]:
        """[count, seconds] for each jitted function compiled or loaded
        among programs number `first` to `last` of this process."""
        out: dict[str, list] = {}
        for n, secs in zip(self.names[first:last], self.durations[first:last]):
            entry = out.setdefault(n, [0, 0.0])
            entry[0] += 1
            entry[1] += secs
        return out

    def read(self) -> dict:
        return {"programs": self.programs, "loaded": self.cache_hits,
                "compile_s": self.seconds}
