"""Persistent XLA compilation cache: one resolver for every entry point.

Every wave/admit/chunk geometry the engine dispatches is a separate XLA
program, and a cold one costs seconds of jit at 1B+ scale. JAX's
persistent compilation cache serializes compiled executables to disk
keyed by HLO hash, so a geometry any PREVIOUS process compiled loads
instead of recompiling. The directory is part of what makes an entry
findable again, so it must be the same in every process:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads its own variable and keeps
  the cache there; this module sets NO cache directory in code.
- unset: `<checkout>/.xla_cache`, derived from this package's location
  (git-ignored). Never the home directory, a temporary name, a pid or a
  time.

Complements, not replaces, the engine's sibling-geometry prewarm
(engine/engine.py prewarm_wave_siblings): the cache kills cross-process
recompiles; the prewarm kills first-ever compiles at a moment nothing is
waiting on them.

Beside the cache, the log of what it did: `COMPILE_LOG`, one per process,
books every program JAX traced, lowered, loaded or compiled, from JAX's own
`jax.monitoring` events (`COMPILE_LOG.install()`, called by
`build_local_backend` before its first jit, on every backend, the CPU's
too). A program the cache holds is still traced and lowered in every new
process: the cache's key is the lowered module's hash. No event fires on a
call of a program already built, so the log costs nothing on the dispatch
path; it works only while JAX compiles.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import os
import threading
from pathlib import Path

logger = logging.getLogger(__name__)

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".xla_cache")


def resolve_compile_cache_dir(path: str | None = "auto") -> str | None:
    """Where the persistent compile cache lives for this process, or None
    when `path` disables it (None / ""). Pure: touches neither JAX nor the
    filesystem. The environment variable, when set, wins over any `path`;
    "auto" is `<checkout>/.xla_cache`; anything else is taken as given."""
    if not path:
        return None
    return os.environ.get(ENV_VAR) or (
        CHECKOUT_CACHE_DIR if path == "auto" else path
    )


def enable_persistent_compile_cache(path: str | None = "auto") -> str | None:
    """Idempotently put JAX's compilation cache at the resolved directory
    and return it (None = nothing enabled here). Safe to call before or
    after jax initialization, from any entry point: the cache directory is
    process-global in jax, so a directory already in effect is kept."""
    resolved = resolve_compile_cache_dir(path)
    if resolved is None or os.environ.get(ENV_VAR):
        return resolved  # disabled, or placed from outside: JAX's own business
    import jax

    if jax.config.jax_compilation_cache_dir:
        return jax.config.jax_compilation_cache_dir
    if jax.default_backend() == "cpu":
        # CPU programs compile in ms (nothing to save) and XLA:CPU's AOT
        # loader logs a page of machine-feature-mismatch warnings per cache
        # hit — the cache only earns its keep on accelerator backends.
        return None
    try:
        os.makedirs(resolved, exist_ok=True)
    except OSError as exc:  # unwritable checkout: serve uncached
        logger.warning("persistent compile cache disabled: %s", exc)
        return None
    jax.config.update("jax_compilation_cache_dir", resolved)
    return resolved


_TRACE_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
_BUILD = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


@dataclasses.dataclass(frozen=True)
class Program:
    """One `backend_compile_duration` event: a program built for a device.

    `name` is JAX's `fun_name` (`jit(wave)` for a `named_program(...,
    program="wave")`); `trace_lower_s` the tracing and lowering booked on
    the same thread since the thread's previous program; `load_compile_s`
    the backend's compile, or on a cache hit the retrieval and load;
    `loaded` whether the persistent cache served it."""

    name: str
    trace_lower_s: float
    load_compile_s: float
    loaded: bool


class CompileLog:
    """Cumulative books of every program this process built.

    Trace and lower events arrive at their END, a nested jit's before the
    jit that holds it (`wave` traces `matmul`, `_reduce_sum`...), each with
    its start and end (`record_event_time_span`). Each thread keeps its
    booked intervals in order of start; an interval that begins at or
    before the latest booked ones encloses them, so their seconds are taken
    back before its own are added: a trace is counted once, however deep.
    A cache hit carries no name; it belongs to the next program built on
    the same thread, as do the trace and lower seconds booked before it."""

    KEEP = 4096  # booked intervals a thread remembers; an outer trace encloses fewer

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._local = threading.local()
        self._installed = False
        self.programs: list[Program] = []
        self.trace_lower_s = 0.0
        self.retrieval_s = 0.0

    def install(self) -> "CompileLog":
        """Register the listeners, once: they stay for the process's life."""
        with self._lock:
            if self._installed:
                return self
            self._installed = True
        import jax.monitoring

        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_time_span_listener(self._time_span)
        return self

    def _thread(self):
        t = self._local
        if not hasattr(t, "booked"):
            t.booked = collections.deque(maxlen=self.KEEP)  # (start, seconds)
            t.pending_s, t.hit = 0.0, False
        return t

    def _event(self, name: str, **_kw) -> None:
        if name == _HIT:
            self._thread().hit = True

    def _time_span(self, name: str, start: float, end: float, **_kw) -> None:
        if name not in _TRACE_LOWER:
            return
        t = self._thread()
        added = end - start
        while t.booked and t.booked[-1][0] >= start:
            added -= t.booked.pop()[1]
        t.booked.append((start, end - start))
        t.pending_s += added
        with self._lock:
            self.trace_lower_s += added

    def _duration(self, name: str, secs: float, **kw) -> None:
        if name == _RETRIEVAL:
            with self._lock:
                self.retrieval_s += secs
        elif name == _BUILD:
            t = self._thread()
            program = Program(str(kw.get("fun_name", "?")), t.pending_s, secs, t.hit)
            t.pending_s, t.hit = 0.0, False
            with self._lock:
                self.programs.append(program)

    def books(self) -> dict[str, float]:
        """Totals since the process began: what `get_stats()["setup"]`
        exports. `programs_compiled` is every program the cache did not
        serve (a miss, or no cache: the CPU's)."""
        with self._lock:
            programs = list(self.programs)
            trace_lower_s, retrieval_s = self.trace_lower_s, self.retrieval_s
        loaded = sum(p.loaded for p in programs)
        return {
            "programs": len(programs),
            "programs_compiled": len(programs) - loaded,
            "programs_loaded": loaded,
            "trace_lower_s": trace_lower_s,
            "load_compile_s": sum(p.load_compile_s for p in programs),
            "retrieval_s": retrieval_s,
        }

    def table(self, first: int = 0, last: int | None = None) -> dict[str, dict]:
        """By `fun_name`, over programs number `first` to `last`: count,
        compiled, loaded, trace_lower_s, load_compile_s."""
        with self._lock:
            programs = self.programs[first:last]
        out: dict[str, dict] = {}
        for p in programs:
            row = out.setdefault(p.name, {"count": 0, "compiled": 0, "loaded": 0,
                                          "trace_lower_s": 0.0, "load_compile_s": 0.0})
            row["count"] += 1
            row["loaded" if p.loaded else "compiled"] += 1
            row["trace_lower_s"] += p.trace_lower_s
            row["load_compile_s"] += p.load_compile_s
        return out


COMPILE_LOG = CompileLog()
