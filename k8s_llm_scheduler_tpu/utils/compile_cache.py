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
"""

from __future__ import annotations

import logging
import os
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
