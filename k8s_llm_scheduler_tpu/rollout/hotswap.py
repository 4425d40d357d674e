"""Zero-downtime weight swap for a running LocalLLMBackend.

The serving engine is single-owner (one engine thread drives every device
dispatch — engine/local.py), so a swap is not a lock dance: it is a control
item on that thread's queue. `LocalLLMBackend.run_quiesced` holds new
admissions, drains every in-flight wave (no request fails or drops — held
work waits out the pause and resumes the next tick), and runs the swap at
the barrier. The admission-held wall time IS the reported swap pause.

Two residency modes, because 2x params does not always fit:

- **double** (default when it fits): restore the candidate direct-to-shard
  onto the SERVING mesh with the existing tp specs while the old params
  keep serving; the quiesced window is only the pointer swap + state
  invalidation (sub-second). The old tree is returned to the caller and
  held until the candidate survives burn-in — instant rollback.
- **donate** (70B-class, no 2x HBM headroom): the old params are released
  FIRST, then the candidate restores into the freed memory inside the
  quiesced window. The pause covers the whole restore, and a failed
  restore leaves the engine paramless — the swapper re-restores the prior
  version from the registry (disk is the rollback buffer, not HBM).

What a swap invalidates (everything computed under the old weights):
- on-device prefix-KV cache + active prefix (engine.swap_params);
- the decision cache ABOVE the engine via a generation bump
  (core/cache.py) — cached decisions are the old policy's outputs and
  must never be served after promotion;
- spec-draft state is per-request (spec/decoder.py) and the target params
  are read live at dispatch, so the paged/spec paths need no extra work.
"""

from __future__ import annotations

import logging
from typing import Any

from k8s_llm_scheduler_tpu.models.loader import CheckpointError, restore_checkpoint
from k8s_llm_scheduler_tpu.observability.trace import PhaseRecorder
from k8s_llm_scheduler_tpu.rollout.registry import (
    CheckpointRegistry,
    RegistryError,
    config_fingerprint,
)

logger = logging.getLogger(__name__)


def swap_engine_params(engine, params) -> Any:
    """Engine-level swap (see InferenceEngine.swap_params): replace the
    served weights and invalidate weight-derived device state. Returns the
    old params tree. Callers outside the engine-owner thread must go
    through HotSwapper / run_quiesced."""
    return engine.swap_params(params)


def _per_device_bytes(params) -> int:
    """Bytes ONE device holds of the tree: a tp-sharded leaf counts its
    shard, a replicated (or single-device) leaf counts whole."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        shards = getattr(leaf, "addressable_shards", None)
        total += int(
            shards[0].data.nbytes if shards else getattr(leaf, "nbytes", 0)
        )
    return total


def _device_headroom_bytes(devices) -> int | None:
    """Least free device memory over `devices` (the serving mesh's — a
    second copy must fit on EVERY one of them), or None when the backend
    doesn't report it (CPU, some drivers) — callers treat None as 'room'."""
    headroom = None
    for dev in devices:
        try:
            stats = dev.memory_stats()
        except Exception:
            return None
        if not stats or "bytes_limit" not in stats:
            return None
        free = int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))
        headroom = free if headroom is None else min(headroom, free)
    return headroom


class HotSwapper:
    """Promote registry versions into a live LocalLLMBackend.

    Owns: digest verification before any restore, config-fingerprint
    matching, residency-mode choice (double vs donate), the quiesced
    install, the decision-cache generation bump, and the swap-pause /
    phase accounting surfaced to /metrics."""

    def __init__(
        self,
        backend,                      # LocalLLMBackend (has .engine, .run_quiesced)
        registry: CheckpointRegistry,
        cfg,                          # the serving LlamaConfig
        *,
        mesh=None,                    # the SERVING mesh (None = single device)
        tp: str | None = "tp",
        fsdp: str | None = None,
        cache=None,                   # DecisionCache to generation-bump
        kvplane=None,                 # fleet KVPlaneStore to generation-bump
        mode: str = "auto",           # auto | double | donate
        quantize: str | None = None,  # None | "int8" — match the serving tree
        verify_digests: bool = True,
    ) -> None:
        if mode not in ("auto", "double", "donate"):
            raise ValueError(f"unknown swap mode {mode!r}")
        if quantize not in (None, "int8"):
            raise ValueError(f"unknown quantization {quantize!r} (only 'int8')")
        self.backend = backend
        self.registry = registry
        self.cfg = cfg
        self.mesh = mesh
        self.tp = tp
        self.fsdp = fsdp
        self.cache = cache
        # Shared prefix-KV plane store: its generation is the FLEET-wide
        # twin of engine.prefix_epoch — peers' published prefix pages
        # were prefilled under the outgoing weights, so the swap must
        # invalidate them everywhere, not just on this replica.
        self.kvplane = kvplane
        self.mode = mode
        self.quantize = quantize
        self.verify_digests = verify_digests
        self.phases = PhaseRecorder()
        self.active_version: int | None = registry.active()
        self._prior_version: int | None = None
        self.stats_counters = {
            "swaps": 0,
            "rollbacks": 0,
            "last_pause_s": 0.0,
            "last_mode": "",
        }

    # ----------------------------------------------------------- residency
    def _choose_mode(self) -> str:
        if self.mode != "auto":
            return self.mode
        import jax

        params_bytes = _per_device_bytes(self.backend.engine.params)
        headroom = _device_headroom_bytes(
            list(self.mesh.devices.flat) if self.mesh is not None
            else jax.local_devices()[:1]
        )
        if headroom is not None and headroom < params_bytes:
            logger.info(
                "swap mode=donate: %.2f GB params per device vs %.2f GB "
                "least HBM headroom (double-buffering needs a full second "
                "copy)",
                params_bytes / 1e9, headroom / 1e9,
            )
            return "donate"
        return "double"

    def _restore(self, manifest) -> Any:
        """Restore a registry version shaped exactly like the serving tree:
        same mesh/specs, same quantization — engine programs were compiled
        against that tree's shardings and dtypes."""
        params = restore_checkpoint(
            manifest.checkpoint_path, self.cfg, self.mesh,
            tp=self.tp, fsdp=self.fsdp,
        )
        if self.quantize == "int8":
            from k8s_llm_scheduler_tpu.models.quant import quantize_params

            params = quantize_params(params)
        return params

    # ---------------------------------------------------------------- swap
    def _check_version(self, version: int) -> "Any":
        manifest = self.registry.get(version)
        if self.verify_digests:
            ok, problems = self.registry.verify(version)
            if not ok:
                raise CheckpointError(
                    f"registry version {version} failed digest verification "
                    f"before swap: {problems[:3]}"
                )
        want = config_fingerprint(self.cfg)
        if manifest.config_fingerprint and manifest.config_fingerprint != want:
            raise CheckpointError(
                f"registry version {version} is shaped for config "
                f"{manifest.config_name!r} (fingerprint "
                f"{manifest.config_fingerprint}), serving config is "
                f"{self.cfg.name!r} ({want})"
            )
        return manifest

    def swap_to(self, version: int) -> dict:
        """Hot-swap the live engine to `version`. Returns
        {"version", "prior", "pause_s", "mode"}. Raises CheckpointError /
        RegistryError with the engine still serving the OLD weights (double
        mode) or restored to them from disk (donate mode)."""
        manifest = self._check_version(version)
        mode = self._choose_mode()
        engine = self.backend.engine
        prior = self.active_version

        if mode == "double":
            # load OUTSIDE the quiesced window: old params serve throughout
            with self.phases.phase("swap_load"):
                new_params = self._restore(manifest)

            def install():
                with self.phases.phase("swap_install"):
                    return engine.swap_params(new_params)

            old_params, pause_s = self.backend.run_quiesced(install)
            # old tree dropped here: burn-in rollback restores from the
            # registry (double-buffering covers the SWAP, not the burn-in —
            # holding 2x HBM for a whole burn-in window would starve the
            # prefix cache)
            del old_params
        else:
            def install():
                with self.phases.phase("swap_install"):
                    engine.params = None  # release before restore: no 2x
                    try:
                        new_params = self._restore(manifest)
                    except Exception:
                        # engine is paramless — restore the prior version
                        # from disk before propagating, or serving is dead
                        if prior is not None:
                            engine.params = self._restore(
                                self.registry.get(prior)
                            )
                        raise
                    return engine.swap_params(new_params)

            _, pause_s = self.backend.run_quiesced(install)

        if self.cache is not None:
            self.cache.bump_generation()
        if self.kvplane is not None:
            self.kvplane.bump_generation()
        self._prior_version = prior
        self.active_version = version
        self.stats_counters["swaps"] += 1
        self.stats_counters["last_pause_s"] = round(pause_s, 6)
        self.stats_counters["last_mode"] = mode
        logger.info(
            "hot-swapped to version %d (mode=%s, pause=%.1f ms, prior=%s)",
            version, mode, pause_s * 1000.0, prior,
        )
        return {
            "version": version,
            "prior": prior,
            "pause_s": pause_s,
            "mode": mode,
        }

    def rollback(self) -> dict:
        """Swap back to the version active before the last swap_to (burn-in
        trip path). Falls back to the active version's manifest parent when
        the in-memory prior is unknown (fresh controller)."""
        target = self._prior_version
        if target is None and self.active_version is not None:
            target = self.registry.get(self.active_version).parent
        if target is None:
            raise RegistryError("no prior version to roll back to")
        out = self.swap_to(target)
        self.stats_counters["rollbacks"] += 1
        return out

    def stats(self) -> dict:
        return {
            **self.stats_counters,
            "active_version": self.active_version,
            "phases": self.phases.snapshot(),
        }
