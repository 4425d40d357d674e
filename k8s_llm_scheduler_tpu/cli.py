"""CLI entry point: run / verify / bench / demo / train / eval / sim / rollout.

Parity surface (reference -> here):
- `python scheduler.py`            -> `python -m k8s_llm_scheduler_tpu.cli run`
  (banner, start, Ctrl-C handling, final stats dump — reference
  scheduler.py:775-823)
- `python verify_setup.py`         -> `... cli verify` (files/env/imports/
  cluster preflight — reference verify_setup.py:28-114; extended with JAX
  device + engine smoke checks, minus any API-token requirement)
- bench harness (reference: none)  -> `... cli bench` (wraps bench.py)
- `... cli demo` runs the full stack against the in-memory fake cluster —
  the zero-dependency path the reference never had.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import importlib
import json
import logging
import sys
import time
from pathlib import Path
from typing import Any

from k8s_llm_scheduler_tpu.config import Config, load_config
from k8s_llm_scheduler_tpu.logging_setup import setup_logging

logger = logging.getLogger(__name__)

BANNER = r"""
  TPU-native LLM Kubernetes Scheduler
  watch -> snapshot -> prompt -> decide(on-TPU) -> validate -> bind
"""


def _backend_kwargs(cfg: Config, **overrides) -> dict:
    """The ONE cfg -> build_local_backend kwargs mapping (cli run/demo and
    cli complete must not drift: a cfg key honored by one and silently
    ignored by the other is a support trap)."""
    kwargs = dict(
        model=cfg.get("llm.model", "tiny"),
        mesh_axes=cfg.get("llm.mesh", None),
        temperature=cfg.get("llm.temperature"),
        max_slots=cfg.get("llm.max_batch"),
        page_size=cfg.get("llm.page_size"),
        prefill_buckets=tuple(cfg.get("llm.prefill_buckets")),
        max_new_tokens=cfg.get("llm.max_tokens"),
        constrained=cfg.get("llm.constrained_json"),
        checkpoint_path=cfg.get("llm.checkpoint_path"),
        tokenizer_path=cfg.get("llm.tokenizer_path"),
        tokenizer_name=cfg.get("llm.tokenizer", "byte"),
        decode_matmul=cfg.get("llm.decode_matmul", "dense"),
        answer_style=cfg.get("llm.answer_style", "direct"),
        max_reason_tokens=int(cfg.get("llm.max_reason_tokens", 320)),
        quantize=cfg.get("llm.quantization"),
        request_timeout_s=float(cfg.get("llm.timeout")),
        group_switch_after_s=float(cfg.get("llm.group_switch_after_s")),
        compile_cache_dir=cfg.get("llm.compile_cache_dir"),
        spec_enabled=bool(cfg.get("llm.spec_enabled", False)),
        spec_arm=cfg.get("llm.spec_arm", "draft"),
        spec_draft_model=cfg.get("llm.spec_draft_model", "tiny"),
        spec_draft_checkpoint=cfg.get("llm.spec_draft_checkpoint", None),
        spec_k=int(cfg.get("llm.spec_k", 4)),
        spec_disable_threshold=float(
            cfg.get("llm.spec_disable_threshold", 0.3)
        ),
        # fused on-device decode runtime (engine/fused/)
        fused_decode=bool(cfg.get("llm.fused_decode", True)),
        top_k=int(cfg.get("llm.top_k", 0)),
        # delta-prefill admission plane (engine/admission/, sched/delta.py)
        packed_admission=bool(cfg.get("admission.packed", True)),
        admission_chunk_tokens=int(cfg.get("admission.chunk_tokens", 256)),
        delta_prompts=bool(cfg.get("admission.delta_prompts", True)),
        repin_fraction=float(cfg.get("admission.repin_fraction", 0.25)),
        max_pins=int(cfg.get("admission.max_pins", 4)),
    )
    if cfg.get("distributed.enabled"):
        # Multi-host: after jax.distributed.initialize, jax.devices() is
        # GLOBAL — a per-host replica mesh built from it would shard params
        # over non-addressable devices (and hang at startup). Each process'
        # backend must span only the devices it owns.
        import jax

        kwargs["devices"] = jax.local_devices()
    kwargs.update(overrides)
    return kwargs


def _build_stack(cfg: Config, cluster) -> Any:
    from k8s_llm_scheduler_tpu.core.breaker import CircuitBreaker
    from k8s_llm_scheduler_tpu.core.cache import DecisionCache
    from k8s_llm_scheduler_tpu.sched.client import DecisionClient
    from k8s_llm_scheduler_tpu.sched.loop import Scheduler

    backend_kind = cfg.get("llm.backend")
    if backend_kind == "stub":
        from k8s_llm_scheduler_tpu.engine.backend import StubBackend

        backend = StubBackend()
    else:
        from k8s_llm_scheduler_tpu.engine.local import build_local_backend

        backend = build_local_backend(**_backend_kwargs(cfg))
    # Coordinator fan-out across worker replicas, when configured
    # (distributed.replica_addrs; sched/replica.py). Sits below the cache/
    # single-flight stack so only leader decisions cross hosts.
    backend = _maybe_fanout(backend, cfg)
    # Disaggregated prefill/decode pools, when configured (fleet.*;
    # fleet/pools.py). Wraps the (possibly fanned-out) backend so
    # admission and continuation route to distinct worker pools.
    backend = _maybe_disaggregate(backend, cfg)
    # Per-decision routing between the big arm (everything built above)
    # and a distilled fast tier, when configured (router.*;
    # sched/router.py). Outermost so routing sees the decision BEFORE
    # any pool/fan-out machinery spends big-arm capacity on it.
    backend = _maybe_router(backend, cfg)

    cache = (
        DecisionCache(
            ttl_seconds=cfg.get("cache.ttl_seconds"),
            max_size=cfg.get("cache.max_size"),
        )
        if cfg.get("cache.enabled")
        else None
    )
    breaker = (
        CircuitBreaker(
            failure_threshold=cfg.get("circuit_breaker.failure_threshold"),
            timeout_seconds=cfg.get("circuit_breaker.timeout"),
            half_open_max_calls=cfg.get("circuit_breaker.half_open_max_calls"),
            cooldown_jitter=float(
                cfg.get("circuit_breaker.cooldown_jitter", 0.1)
            ),
        )
        if cfg.get("circuit_breaker.enabled")
        else None
    )
    # deadline-budgeted degradation ladder (sched/deadline.py). The env
    # override arrives as a STRING (the default is null, so _coerce has
    # no type template): normalize through float FIRST, then apply the
    # documented "null / <=0 disables" semantics — `in (None, 0)` would
    # let SCHED_DECISION_DEADLINE_MS=0 slip through as a 0ms deadline
    # that sheds every decision fleet-wide.
    deadline_ms = cfg.get("scheduler.decision_deadline_ms", None)
    if deadline_ms is not None:
        deadline_ms = float(deadline_ms)
        if deadline_ms <= 0:
            deadline_ms = None
    client = DecisionClient(
        backend,
        cache=cache,
        breaker=breaker,
        max_retries=cfg.get("llm.max_retries"),
        retry_delay=cfg.get("llm.retry_delay"),
        fallback_strategy=cfg.get("fallback.strategy"),
        fallback_enabled=cfg.get("fallback.enabled"),
        deadline_ms=deadline_ms,
        llm_min_budget_ms=float(
            cfg.get("scheduler.llm_min_budget_ms", 25.0)
        ),
    )
    scheduler = Scheduler(
        cluster, cluster, client,
        scheduler_name=cfg.get("scheduler.name"),
        error_backoff_s=cfg.get("scheduler.error_backoff_seconds"),
        prefix_prewarm_s=float(
            cfg.get("scheduler.prefix_prewarm_seconds", 0.25)
        ),
    )
    return scheduler, backend


def _maybe_journal(cfg: Config):
    """Build the durable decision journal when the `durability` block
    enables it (sched/journal.py); None otherwise."""
    if not cfg.get("durability.enabled", False):
        return None
    journal_dir = cfg.get("durability.journal_dir", None)
    if not journal_dir:
        raise SystemExit(
            "durability.enabled is set but durability.journal_dir is not "
            "(DURABILITY_JOURNAL_DIR)"
        )
    from k8s_llm_scheduler_tpu.sched.journal import DecisionJournal

    return DecisionJournal(
        journal_dir,
        fsync_policy=str(cfg.get("durability.fsync", "intent")),
        segment_max_records=int(
            cfg.get("durability.segment_max_records", 4096)
        ),
    )


def _recovery_lookup(cluster):
    """The cluster-truth probe recovery needs (sched/recovery.PodLookup),
    from whatever cluster driver is in play."""
    factory = getattr(cluster, "recovery_lookup", None)  # KubeCluster
    if factory is not None:
        return factory()  # one list snapshot answers the whole pass
    get_pod = getattr(cluster, "get_pod", None)  # FakeCluster

    def lookup(ns: str, name: str):
        raw = get_pod(ns, name)
        if raw is None:
            return ("gone", None)
        if raw.node_name:
            return ("bound", raw.node_name)
        return ("pending", None)

    return lookup


async def _run_scheduler(
    cfg: Config, cluster, demo_pods: bool = False, journal=None,
) -> int:
    scheduler, backend = _build_stack(cfg, cluster)

    if journal is not None:
        # Durable decision plane (sched/journal.py + sched/recovery.py):
        # the binder journals the decide/intent/ack lifecycle, the
        # breaker journals its trips, and recovery reconciles whatever a
        # previous incarnation left open BEFORE the watch starts — a
        # decided-but-unbound pod completes without a model call, a
        # bound-but-unacked one just gets its ack.
        from k8s_llm_scheduler_tpu.sched import recovery as recovery_mod
        from k8s_llm_scheduler_tpu.sched.recovery import JournaledBinder

        scheduler.binder = JournaledBinder(scheduler.binder, journal)
        if scheduler.client.breaker is not None:
            scheduler.client.breaker.journal_sink = journal.record_breaker
        report = await asyncio.to_thread(
            recovery_mod.recover,
            journal,
            pod_lookup=_recovery_lookup(cluster),
            binder=scheduler.binder,
            breaker=scheduler.client.breaker,
        )
        logger.info(
            "journal recovery: %d acked, %d completed, %d dropped, "
            "%d refused (resume rv=%s)",
            report.acked, report.rebound, report.dropped, report.failed,
            report.resume_rv,
        )

    engine = getattr(backend, "engine", None)
    profiler = None
    if engine is not None and cfg.get("observability.profiler", True):
        # Continuous wave profiler (observability/profiler.py): per-wave
        # dispatch/sync fencing + MFU loss decomposition, served at
        # /debug/profile and as llm_scheduler_engine_profile_* gauges.
        from k8s_llm_scheduler_tpu.observability.profiler import (
            EngineProfiler,
        )

        profiler = EngineProfiler(
            cfg=engine.cfg,
            window=int(cfg.get("observability.profiler_window", 256)),
        )
        engine.attach_profiler(profiler)

    # SLO burn-rate engine (observability/slo.py): declarative objectives
    # from the `slo` config block evaluated over multi-window burn rates;
    # trips surface at /debug/slo, as gauges, and as an ADVISORY into the
    # circuit breaker (never a forced state change). The stats tree it
    # reads embeds the profiler's gauges under `engine_profile` — the
    # cumulative segment counters (queue_stall_ms_total et al.) make a
    # throughput/pressure objective expressible straight from config
    # (numerator engine_profile.queue_stall_ms_total over
    # engine_profile.wall_ms_cum_total), with no custom provider;
    # before this the segment books were reachable only via
    # /debug/profile.
    from k8s_llm_scheduler_tpu.observability import slo as slo_mod

    slo_stats_provider = scheduler.get_stats
    if profiler is not None:
        def slo_stats_provider(_base=scheduler.get_stats, _prof=profiler):
            return {**_base(), "engine_profile": _prof.gauges()}

    slo_engine = slo_mod.from_config(cfg.section("slo"), slo_stats_provider)
    if slo_engine is not None:
        breaker = scheduler.client.breaker
        if breaker is not None:
            slo_engine.on_trip.append(
                lambda name, _detail: breaker.slo_advisory(name)
            )
        if cfg.get("slo.brownout", True):
            # burn-rate brownout (sched/client.py): a sustained burn
            # sheds the LLM rung fleet-wide until the burn clears — the
            # falling edge matters as much as the rising one, or one
            # trip would degrade decisions forever
            client = scheduler.client
            slo_engine.on_trip.append(
                lambda name, _d: client.enter_brownout(f"slo:{name}")
            )
            slo_engine.on_clear.append(
                lambda name, _d: client.exit_brownout(f"slo:{name}")
            )
        slo_engine.start(interval_s=float(cfg.get("slo.interval_s", 10.0)))

    metrics_server = None
    sampler = None
    if cfg.get("metrics.enabled"):
        from k8s_llm_scheduler_tpu.observability.metrics import MetricsServer

        stats_provider = scheduler.get_stats
        if engine is not None:
            # Background engine telemetry (observability/sampler.py): ring
            # series of occupancy / KV utilization / prefix hit rate /
            # tokens-per-s / HBM watermark, served at /debug/engine with
            # the latest values merged into /metrics as gauges.
            from k8s_llm_scheduler_tpu.observability.sampler import (
                EngineSampler,
            )

            sampler = EngineSampler(
                engine,
                interval_s=float(
                    cfg.get("observability.sampler_interval_s", 1.0)
                ),
                window=int(cfg.get("observability.sampler_window", 600)),
            )
            sampler.start()
            base_provider = scheduler.get_stats

            def stats_provider(
                _base=base_provider, _sampler=sampler,
            ):
                return {**_base(), "engine_telemetry": _sampler.latest()}

        metrics_server = MetricsServer(
            stats_provider,
            port=cfg.get("metrics.port"),
            is_alive=lambda: scheduler.running,
            engine_sampler=sampler,
            engine_profiler=profiler,
            slo_engine=slo_engine,
        )
        metrics_server.start()

    if demo_pods:
        from k8s_llm_scheduler_tpu.testing import fixture_pods

        for pod in fixture_pods(cfg.get("scheduler.name")):
            cluster.add_pod(pod)

    print(BANNER)
    logger.info("scheduler %r starting", cfg.get("scheduler.name"))
    task = asyncio.create_task(scheduler.run())
    try:
        if demo_pods:
            while cluster.bind_count < 3:
                await asyncio.sleep(0.05)
            logger.info("demo: all fixture pods scheduled")
            scheduler.stop()
            cluster.close()
        await task
    except (KeyboardInterrupt, asyncio.CancelledError):
        logger.info("shutting down")
        scheduler.stop()
        close = getattr(cluster, "close", None)
        if close:
            close()
        await asyncio.wait_for(task, timeout=30)
    finally:
        # Shutdown ordering (lifecycle contract, tests/test_profiler.py):
        # background samplers/evaluators stop-and-join FIRST (no thread
        # may sample an engine mid-teardown), then the metrics server
        # (whose stop also covers both — idempotent), then the backend
        # close flushes the profiler's in-flight fences.
        if sampler is not None:
            sampler.stop()
        if slo_engine is not None:
            slo_engine.stop()
        if metrics_server:
            metrics_server.stop()
        close_backend = getattr(backend, "close", None)
        if close_backend:
            close_backend()
        if journal is not None:
            journal.close()
        # Final stats dump (reference scheduler.py:803-819).
        print(json.dumps(scheduler.get_stats(), indent=2, default=str))
    return 0


def _maybe_init_distributed(cfg: Config) -> bool:
    """Initialize multi-host JAX when configured. Returns True when this
    process should run the cluster-facing control plane (always True
    single-process; process 0 only otherwise)."""
    if not cfg.get("distributed.enabled"):
        return True
    from k8s_llm_scheduler_tpu.parallel.distributed import (
        init_distributed,
        is_coordinator,
    )

    init_distributed(
        cfg.get("distributed.coordinator"),
        cfg.get("distributed.num_processes"),
        cfg.get("distributed.process_id"),
    )
    return is_coordinator()


def cmd_run(args: argparse.Namespace, cfg: Config) -> int:
    if not _maybe_init_distributed(cfg):
        # Worker host: no control plane (watch/bind belongs to the
        # coordinator alone) — serve THIS host's model replica over the
        # decision-RPC transport until terminated (SCALING.md
        # "Multi-host"; sched/replica.py).
        return _run_worker_replica(cfg)
    journal = _maybe_journal(cfg)
    if args.fake_cluster:
        from k8s_llm_scheduler_tpu.testing import synthetic_cluster

        cluster = synthetic_cluster(args.fake_nodes)
    else:
        from k8s_llm_scheduler_tpu.cluster.kube import KubeCluster

        kube_kwargs = {}
        if journal is not None:
            # resume the watch after the journaled resourceVersion (one
            # reconciling relist covers anything older) and keep the
            # journal's resume point current as events stream
            kube_kwargs = {
                "resume_rv": journal.state.last_rv,
                "rv_hook": journal.record_rv,
            }
        try:
            cluster = KubeCluster(
                watch_timeout_seconds=cfg.get("scheduler.watch_interval"),
                **kube_kwargs,
            )
        except Exception as exc:
            # a driver is always importable (in-tree httpapi fallback);
            # a missing/unreachable kubeconfig surfaces here
            print(
                f"cannot reach a Kubernetes cluster ({exc}); use "
                f"--fake-cluster for the in-memory cluster",
                file=sys.stderr,
            )
            return 2
    return asyncio.run(
        _run_scheduler(cfg, cluster, demo_pods=False, journal=journal)
    )


def _run_worker_replica(
    cfg: Config, stop_event: Any | None = None, ready: Any | None = None
) -> int:
    """Worker-process serving loop: build the local backend (weights for
    THIS host's replica; tp within the host, over THIS process' local
    devices — `_backend_kwargs` injects `devices=jax.local_devices()` when
    distributed.enabled) and answer decision RPCs from the coordinator
    until the process is terminated.

    `stop_event`/`ready` exist for tests (tests/test_multihost.py drives
    this exact path with a tp=2 mesh): production workers pass neither and
    serve until killed."""
    import threading

    from k8s_llm_scheduler_tpu.sched.replica import ReplicaServer

    if cfg.get("llm.backend") == "stub":
        # control-plane testing without weights: workers honor the stub
        # setting exactly like the coordinator's _build_stack does
        from k8s_llm_scheduler_tpu.engine.backend import StubBackend

        backend = StubBackend()
    else:
        from k8s_llm_scheduler_tpu.engine.local import build_local_backend

        backend = build_local_backend(**_backend_kwargs(cfg))
    port = int(cfg.get("distributed.replica_port"))
    server = ReplicaServer(
        backend,
        host=str(cfg.get("distributed.replica_bind_host")),
        port=port,
        max_inflight=int(cfg.get("distributed.replica_max_inflight")),
    )
    print(f"replica worker serving decisions on :{server.port}", flush=True)
    if ready is not None:
        ready.port = server.port
        ready.set()
    try:
        (stop_event or threading.Event()).wait()  # serve until terminated
    except KeyboardInterrupt:
        pass
    finally:
        server.close()
        backend.close()
    return 0


def _parse_replica_addr(
    text: str, default_port: int, key: str
) -> tuple[str, int]:
    """Parse one replica-address config entry into (host, port)."""
    if text.isdigit():
        # bare port (pre-round-4 configs used '9901' for
        # localhost:9901 — keep that meaning rather than dialing a
        # hostname made of digits)
        return "localhost", int(text)
    if text.startswith("["):
        # bracketed IPv6: '[::1]:9901' or '[::1]' (default port)
        bracket_end = text.find("]")
        if bracket_end < 0:
            raise ValueError(
                f"{key} entry {text!r}: unterminated "
                f"'[' (expected '[v6-addr]:port')"
            )
        host = text[1:bracket_end]
        rest = text[bracket_end + 1 :]
        if rest.startswith(":"):
            try:
                port = int(rest[1:])
            except ValueError:
                raise ValueError(
                    f"{key} entry {text!r}: port "
                    f"{rest[1:]!r} is not an integer"
                ) from None
        elif rest:
            raise ValueError(
                f"{key} entry {text!r}: trailing "
                f"{rest!r} after ']' (expected '[v6-addr]:port')"
            )
        else:
            port = default_port
        return host, port
    if text.count(":") > 1:
        # bare IPv6 literal: rpartition(':') would misparse '::1' as
        # host ':' port 1 — demand brackets instead of guessing
        raise ValueError(
            f"{key} entry {text!r} looks like a bare "
            f"IPv6 literal; write it bracketed ('[{text}]:port')"
        )
    host, sep, port_s = text.rpartition(":")
    if sep:
        try:
            port = int(port_s)
        except ValueError:
            raise ValueError(
                f"{key} entry {text!r}: port "
                f"{port_s!r} is not an integer (expected 'host:port' "
                f"or bare 'host')"
            ) from None
    else:
        host, port = text, default_port  # bare host: default port
    return host or "localhost", port


def _replica_clients(cfg: Config, addrs, key: str) -> list:
    from k8s_llm_scheduler_tpu.sched.replica import ReplicaClient

    default_port = int(cfg.get("distributed.replica_port"))
    timeout_s = float(cfg.get("llm.timeout"))
    return [
        ReplicaClient(
            *_parse_replica_addr(str(addr), default_port, key),
            request_timeout_s=timeout_s,
        )
        for addr in addrs
    ]


def _maybe_fanout(backend, cfg: Config):
    """Wrap the coordinator's backend in a FanoutBackend when worker
    replica addresses are configured."""
    addrs = cfg.get("distributed.replica_addrs") or []
    if not addrs:
        return backend
    from k8s_llm_scheduler_tpu.sched.replica import FanoutBackend

    replicas = [backend] + _replica_clients(
        cfg, addrs, "distributed.replica_addrs"
    )
    logger.info("fanning decisions out over %d replicas", len(replicas))
    return FanoutBackend(replicas)


def _maybe_disaggregate(backend, cfg: Config):
    """Wrap the backend in a DisaggregatedBackend when fleet pools are
    configured: prefill workers absorb admission bursts (prepacked),
    decode workers keep continuation latency flat. The local backend
    always serves in the prefill pool; with no pool addresses (or
    fleet.enabled off) this is a no-op."""
    if not cfg.get("fleet.enabled"):
        return backend
    prefill_addrs = cfg.get("fleet.prefill_addrs") or []
    decode_addrs = cfg.get("fleet.decode_addrs") or []
    if not prefill_addrs and not decode_addrs:
        return backend
    from k8s_llm_scheduler_tpu.fleet import DisaggregatedBackend

    prefill_pool = [backend] + _replica_clients(
        cfg, prefill_addrs, "fleet.prefill_addrs"
    )
    decode_pool = _replica_clients(cfg, decode_addrs, "fleet.decode_addrs")
    logger.info(
        "disaggregated pools: %d prefill / %d decode worker(s)",
        len(prefill_pool), len(decode_pool),
    )
    return DisaggregatedBackend(
        prefill_pool,
        decode_pool,
        prepack_max_batch=int(cfg.get("fleet.prepack_max_batch")),
        prepack_window_s=float(cfg.get("fleet.prepack_window_ms")) / 1000.0,
    )


def _maybe_router(backend, cfg: Config):
    """Wrap the backend in a RoutedBackend when router.enabled: the big
    arm is whatever stack was built above (sharded local engine, fan-out,
    disaggregated pools); the fast arm is a small distilled model served
    locally (router.fast_model / router.fast_checkpoint). No-op when the
    big arm is a stub — routing a stub to a stub measures nothing."""
    if not cfg.get("router.enabled"):
        return backend
    if cfg.get("llm.backend") == "stub":
        logger.warning("router.enabled ignored: llm.backend is stub")
        return backend
    from k8s_llm_scheduler_tpu.engine.local import build_local_backend
    from k8s_llm_scheduler_tpu.models.configs import get_config
    from k8s_llm_scheduler_tpu.sched.router import RoutedBackend, RouterPolicy

    fast = build_local_backend(**_backend_kwargs(
        cfg,
        model=cfg.get("router.fast_model", "tiny"),
        # the fast arm is deliberately single-device: its whole point is
        # no cross-chip collectives on the latency path
        mesh_axes=None,
        checkpoint_path=cfg.get("router.fast_checkpoint"),
        tokenizer_name=cfg.get("router.fast_tokenizer", "numeric"),
        quantize=None,
    ))
    policy = RouterPolicy(
        big_min_budget_ms=float(cfg.get("router.big_min_budget_ms", 120.0)),
        big_cold_extra_ms=float(cfg.get("router.big_cold_extra_ms", 250.0)),
        complexity_threshold=int(cfg.get("router.complexity_threshold", 2)),
        prewarm_on_cold=bool(cfg.get("router.prewarm_on_cold", True)),
    )
    logger.info(
        "routing decisions: big=%s fast=%s (min budget %.0fms, "
        "complexity >= %d)",
        cfg.get("llm.model", "tiny"), cfg.get("router.fast_model", "tiny"),
        policy.big_min_budget_ms, policy.complexity_threshold,
    )
    return RoutedBackend(backend, fast, policy)


def cmd_demo(args: argparse.Namespace, cfg: Config) -> int:
    from k8s_llm_scheduler_tpu.testing import synthetic_cluster

    cluster = synthetic_cluster(args.fake_nodes)
    return asyncio.run(_run_scheduler(cfg, cluster, demo_pods=True))


def cmd_verify(args: argparse.Namespace, cfg: Config) -> int:
    """Preflight (reference verify_setup.py:28-114, TPU edition)."""
    failures = []

    def check(name: str, fn) -> None:
        try:
            detail = fn()
            print(f"  [ok] {name}" + (f" — {detail}" if detail else ""))
        except Exception as exc:
            failures.append((name, exc))
            print(f"  [FAIL] {name}: {exc}")

    print("Preflight checks:")
    for mod in ("jax", "numpy", "yaml", "optax"):
        check(f"import {mod}", lambda m=mod: importlib.import_module(m).__name__)
    check("jax devices", lambda: str(__import__("jax").devices()))
    check("config resolves", lambda: f"scheduler={cfg.get('scheduler.name')}")

    def engine_smoke():
        import jax as _jax
        import jax.numpy as _jnp

        from k8s_llm_scheduler_tpu.models.configs import TINY
        from k8s_llm_scheduler_tpu.models.llama import forward_prefill, init_params

        params = init_params(_jax.random.PRNGKey(0), TINY)
        logits, _, _ = _jax.jit(forward_prefill, static_argnums=(1,))(
            params, TINY, _jnp.zeros((1, 16), _jnp.int32), _jnp.array([16])
        )
        return f"forward ok {logits.shape}"

    if not args.fast:
        check("model forward (TINY)", engine_smoke)

    def kube_check():
        import os

        from k8s_llm_scheduler_tpu.cluster.kube import KubeCluster

        configured = (
            os.environ.get("KUBERNETES_SERVICE_HOST")
            or os.environ.get("KUBECONFIG")
            or os.path.exists(os.path.expanduser("~/.kube/config"))
        )
        if not configured:
            # a driver is always importable (in-tree httpapi fallback);
            # only call out to a cluster when one is actually configured
            return (
                f"no kubeconfig found (driver {KubeCluster.driver()}; "
                f"fake cluster available)"
            )
        nodes = KubeCluster().get_node_metrics()
        return f"{len(nodes)} nodes visible ({KubeCluster.driver()} driver)"

    check("cluster access", kube_check)

    def tokenizer_check():
        from pathlib import Path

        # Resolve EXACTLY like engine/local.build_local_backend: explicit
        # tokenizer_path, else the checkpoint dir when it bundles one, else
        # the runtime falls back to the hermetic ByteTokenizer (in which
        # case the bundled BPE fixture is checked as a packaging smoke).
        path = cfg.get("llm.tokenizer_path")
        label = "configured tokenizer"
        if not path:
            ckpt = cfg.get("llm.checkpoint_path")
            if ckpt and (Path(ckpt) / "tokenizer.json").exists():
                path, label = ckpt, "checkpoint tokenizer"
        if not path:
            path = str(Path(__file__).resolve().parent / "assets" / "bpe4k")
            label = "bundled BPE fixture (runtime default is ByteTokenizer)"
        try:
            from k8s_llm_scheduler_tpu.engine.tokenizer import HFTokenizerAdapter

            tok = HFTokenizerAdapter(path)
        except ImportError:
            # tokenizers and jinja2 are the optional `hf` extra; the hermetic
            # byte-level path needs no files (mirror kube_check's degrade).
            return "tokenizers/jinja2 not installed (ByteTokenizer available)"
        sample = "Node: node-1"
        if tok.decode(tok.encode(sample)) != sample:
            raise RuntimeError(f"tokenizer round-trip failed for {sample!r}")
        return f"{label}: vocab {tok.vocab_size}, pad {tok.pad_id}, eos {tok.eos_id}"

    if not args.fast:
        check("tokenizer loads + round-trips", tokenizer_check)

    if failures:
        print(f"\n{len(failures)} check(s) failed")
        return 1
    print("\nall checks passed")
    return 0


def cmd_train(args: argparse.Namespace, cfg: Config) -> int:
    """Fine-tune the decision model on heuristic-teacher pairs and save an
    orbax checkpoint servable via llm.checkpoint_path (train/distill.py)."""
    from k8s_llm_scheduler_tpu.models.configs import get_config
    from k8s_llm_scheduler_tpu.train.distill import train_and_save

    if cfg.get("llm.answer_style", "direct") != "cot" and (
        args.micro_frac or args.cot_weight != 1.0
    ):
        # these knobs only shape CoT batches; silently ignoring them
        # would waste a multi-hour run (reviewer finding)
        print(
            "--micro-frac/--cot-weight require llm.answer_style: cot "
            "(set it in the config or LLM_ANSWER_STYLE)",
            file=sys.stderr,
        )
        return 2
    # Training is SPMD: every process enters the same step (dp/fsdp axes
    # may span hosts via parallel/distributed.multihost_mesh).
    _maybe_init_distributed(cfg)
    model_cfg = get_config(args.model)
    loss = train_and_save(
        model_cfg,
        out_dir=args.out,
        steps=args.steps,
        batch_size=args.batch_size,
        seq_len=args.seq_len,
        mesh_axes=cfg.get("llm.mesh"),
        lr=args.lr,
        tokenizer_name=cfg.get("llm.tokenizer", "byte"),
        name_weight=args.name_weight,
        probe_every=args.probe_every,
        lr_schedule=args.lr_schedule,
        easy_frac=args.easy_frac,
        save_every=args.save_every,
        resume=args.resume,
        answer_style=cfg.get("llm.answer_style", "direct"),
        cot_weight=args.cot_weight,
        micro_frac=args.micro_frac,
        prompt_lm_frac=args.prompt_lm_frac,
        placement_frac=args.placement_frac,
        diverse_frac=args.diverse_frac,
        seed=args.seed,
        registry_dir=(
            None if args.no_publish
            else args.registry or cfg.get("rollout.registry_dir", None)
        ),
    )
    print(f"final loss {loss:.4f}; checkpoint at {args.out}")
    if args.eval:
        import jax

        if jax.process_index() != 0:
            # Multi-host SPMD training: the serving-stack eval is a
            # single-process affair (worker processes must not each build
            # a backend over a mesh that spans hosts, nor print duplicate
            # reports).
            return 0
        from k8s_llm_scheduler_tpu.train.eval import evaluate_checkpoint

        report = evaluate_checkpoint(
            args.model, args.out, n_cases=args.eval_cases,
            backend_kwargs=_eval_backend_kwargs(cfg),  # greedy report card
        )
        print(json.dumps(report))
    return 0


def _eval_backend_kwargs(cfg: Config, temperature: float = 0.0) -> dict:
    """The cfg mapping for eval backends, minus multi-host mesh axes (the
    eval is per-process; a dcn-spanning llm.mesh would reference
    non-addressable devices).

    `temperature` is an EVAL parameter, not serving config: the report
    card defaults to GREEDY so the measurement is deterministic and
    reproducible run to run (`cli eval --temperature` opts into sampled
    measurement). Production serving keeps llm.temperature untouched.
    (EVAL.md round 5: with the token budget sized right, this checkpoint
    measures 100% at both 0.0 and the serving default 0.3 — the greedy
    default is about determinism, not a quality cliff.)"""
    import jax

    kwargs = _backend_kwargs(cfg)
    if jax.process_count() > 1:
        kwargs["mesh_axes"] = None
    kwargs["temperature"] = temperature
    return kwargs


def cmd_eval(args: argparse.Namespace, cfg: Config) -> int:
    """Decision-quality report card (train/eval.py): teacher agreement on
    held-out clusters + placement load-spread vs the fallback scorer and a
    random placer — the criteria the reference only PROMPTS for
    (reference scheduler.py:196-214), measured."""
    from k8s_llm_scheduler_tpu.train.eval import evaluate_checkpoint

    report = evaluate_checkpoint(
        args.model or cfg.get("llm.model", "tiny"),
        args.checkpoint,
        n_cases=args.cases,
        placement_pods=args.placement_pods,
        backend_kwargs=_eval_backend_kwargs(cfg, temperature=args.temperature),
        scenarios=args.scenarios,
        scenario_cases_n=args.scenario_cases,
    )
    print(json.dumps(report))
    if args.scenarios and report.get("scenarios"):
        # human-readable table after the JSON line
        print(f"{'scenario':<18}{'agree%':>8}{'chance%':>9}{'valid%':>8}{'n':>5}",
              file=sys.stderr)
        for kind, row in report["scenarios"].items():
            print(
                f"{kind:<18}{row['agreement_pct']:>8}{row['chance_pct']:>9}"
                f"{row['valid_pct']:>8}{row['n_cases']:>5}",
                file=sys.stderr,
            )
    return 0


def cmd_bench(args: argparse.Namespace, cfg: Config) -> int:
    # One process per chip: bench.py is the process that holds it. Nothing
    # on the way here (config, logging, spans) initialises a JAX backend —
    # a parent that had touched jax.devices() would keep the chip from the
    # child.
    import subprocess

    cmd = [sys.executable, "bench.py"] + args.bench_args
    return subprocess.call(cmd)


def _sim_arms(args: argparse.Namespace, cfg: Config) -> list:
    """Arm names -> ArmSpecs. `llm` serves the CONFIGURED decision backend
    (llm.backend: local builds the real engine with temperature forced to
    0 — greedy, so the arena's determinism contract holds; stub is the
    zero-weights stand-in). `stub` always means StubBackend through the
    full stack. Heuristic names come from core/fallback.SCORERS; `teacher`
    is the sim/teacher.py reference policy."""
    from k8s_llm_scheduler_tpu.core.fallback import SCORERS
    from k8s_llm_scheduler_tpu.sim import ArmSpec, HeuristicBackend, teacher_arm

    specs: list = []
    for name in [a.strip() for a in args.arms.split(",") if a.strip()]:
        if name == "llm":
            if cfg.get("llm.backend") == "stub":
                from k8s_llm_scheduler_tpu.engine.backend import StubBackend

                specs.append(ArmSpec(name="llm", kind="stack", make=StubBackend))
            else:
                def make_llm():
                    from k8s_llm_scheduler_tpu.engine.local import (
                        build_local_backend,
                    )

                    return build_local_backend(
                        **_backend_kwargs(cfg, temperature=0.0)
                    )

                specs.append(ArmSpec(name="llm", kind="stack", make=make_llm))
        elif name == "stub":
            from k8s_llm_scheduler_tpu.engine.backend import StubBackend

            specs.append(ArmSpec(name="stub", kind="stack", make=StubBackend))
        elif name == "teacher":
            specs.append(teacher_arm())
        elif name in SCORERS:
            specs.append(
                ArmSpec(
                    name=name, kind="stack",
                    make=lambda n=name: HeuristicBackend(n),
                )
            )
        else:
            raise SystemExit(
                f"unknown arm {name!r} (known: llm, stub, teacher, "
                f"{', '.join(SCORERS)})"
            )
    return specs


def cmd_sim(args: argparse.Namespace, cfg: Config) -> int:
    """Cluster-twin scenario arena (sim/): seeded burst/Poisson workloads
    through the REAL stack over the wire-level fake API server, scored
    across decision arms, recorded as a bit-identically replayable trace."""
    from k8s_llm_scheduler_tpu.sim import (
        ChurnEvent,
        ScenarioSpec,
        generate_scenario,
        run_arena,
        save_trace,
        verify_trace,
    )

    if args.replay:
        ok, detail = verify_trace(args.replay)
        print(json.dumps({
            "metric": "sim_replay", "ok": ok, "trace": args.replay,
            "detail": detail,
        }))
        return 0 if ok else 1

    churn = []
    for entry in args.churn or []:
        try:
            wave_s, kind, node = entry.split(":", 2)
            churn.append(ChurnEvent(wave=int(wave_s), kind=kind, node=node))
        except ValueError:
            raise SystemExit(
                f"--churn {entry!r}: expected WAVE:KIND:NODE "
                f"(e.g. 2:fail:sim-node-003)"
            ) from None
    spec = ScenarioSpec(
        name=args.name,
        seed=args.seed,
        n_nodes=args.nodes,
        n_pods=args.pods,
        shapes=args.shapes,
        arrival=args.arrival,
        arrival_rate=args.arrival_rate,
        n_waves=args.waves,
        hetero=not args.homogeneous,
        taint_frac=args.taint_frac,
        constraint_mix=tuple(
            c.strip() for c in args.constraints.split(",") if c.strip()
        ),
        churn=tuple(churn),
    )
    try:
        scenario = generate_scenario(spec)
    except ValueError as exc:
        raise SystemExit(str(exc)) from None
    arms = _sim_arms(args, cfg)

    live: dict[str, Any] = {"arena": {"done_arms": 0, "arms": {}}}
    metrics_server = None
    if args.metrics_port is not None:
        from k8s_llm_scheduler_tpu.observability.metrics import MetricsServer

        metrics_server = MetricsServer(
            lambda: live["arena"], port=args.metrics_port
        )
        metrics_server.start()

    def on_arm_done(name: str, arm_report: dict) -> None:
        live["arena"]["done_arms"] += 1
        live["arena"]["arms"][name] = {
            "scores": arm_report["scores"],
            "waves": arm_report["waves"],
        }
        print(json.dumps({
            "metric": "sim_arm",
            "arm": name,
            "scores": arm_report["scores"],
            "placements_digest": arm_report["placements_digest"],
        }), flush=True)

    try:
        report = run_arena(
            scenario, arms,
            wave_timeout_s=args.wave_timeout,
            on_arm_done=on_arm_done,
        )
    finally:
        if metrics_server is not None:
            metrics_server.stop()

    if args.trace:
        save_trace(report, args.trace)
    report.pop("_traces")
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:  # graftlint: ok[nonatomic-state-write] — operator-requested report path, not runtime state; a torn copy is re-runnable
            json.dump(report, fh, indent=1, sort_keys=True)
    # headline: one line, deterministic fields only
    print(json.dumps({
        "metric": "sim_arena",
        "seed": spec.seed,
        "nodes": spec.n_nodes,
        "pods": spec.n_pods,
        "waves": len(scenario.waves),
        "arms": {
            name: {
                "spread": arm["scores"]["spread"],
                "bound_frac": arm["scores"]["bound_frac"],
                "constraint_satisfaction":
                    arm["scores"]["constraint_satisfaction"],
                "placements_digest": arm["placements_digest"],
            }
            for name, arm in report["arms"].items()
        },
    }))
    return 0


def cmd_chaos(args: argparse.Namespace, cfg: Config) -> int:
    """Deterministic chaos plane (chaos/): seeded fault schedules over
    the real stack, invariant-monitored, replayable byte-for-byte."""
    from k8s_llm_scheduler_tpu.chaos import (
        REGIMES,
        run_chaos,
        save_chaos_trace,
        verify_chaos_trace,
    )

    if args.chaos_cmd == "list":
        for name in sorted(REGIMES):
            info = REGIMES[name]
            print(f"{name:18s} [{info['mode']:6s}] {info['describe']}")
        return 0

    if args.chaos_cmd == "replay":
        ok, detail = verify_chaos_trace(args.trace)
        print(json.dumps({
            "metric": "chaos_replay", "ok": ok, "trace": args.trace,
            "detail": detail,
        }))
        return 0 if ok else 1

    # run
    regimes = sorted(REGIMES) if args.regime == "all" else [args.regime]
    unknown = [r for r in regimes if r not in REGIMES]
    if unknown:
        raise SystemExit(
            f"unknown regime(s) {unknown}; `cli chaos list` shows all"
        )
    if args.trace and len(regimes) != 1:
        raise SystemExit("--trace records exactly one regime's run")
    deadline_ms = args.deadline_ms
    if deadline_ms is not None and deadline_ms <= 0:
        deadline_ms = None
    exit_code = 0
    for regime in regimes:
        report = run_chaos(
            regime, seed=args.seed,
            n_waves=args.waves, n_nodes=args.nodes,
            n_pods=args.pods,
            wave_timeout_s=args.wave_timeout,
            deadline_ms=deadline_ms,
        )
        if args.trace:
            save_chaos_trace(report, args.trace)
        if args.out:
            mode = "w" if regime == regimes[0] else "a"  # JSONL, one run per line
            with open(args.out, mode, encoding="utf-8") as fh:
                json.dump(report, fh, sort_keys=True)
                fh.write("\n")
        clean = report["invariants"]["clean"]
        if not clean:
            exit_code = 1
            for v in report["invariants"]["violations"]:
                line = f"VIOLATION [{v['invariant']}] {v['subject']}: {v['detail']}"
                if v.get("trace_id"):
                    line += f" (cli trace show {v['trace_id']})"
                print(line, flush=True)
        print(json.dumps({
            "metric": "chaos",
            "regime": regime,
            "seed": args.seed,
            "mode": report["mode"],
            "clean": clean,
            "plan_digest": report["plan_digest"],
            "bound_frac": report["scores"]["bound_frac"],
            "degraded_fraction": report["degraded_fraction"],
            "recovery_waves": report["recovery"]["recovery_waves"],
            "injections": report["injections"],
        }), flush=True)
    return exit_code


def cmd_journal(args: argparse.Namespace, cfg: Config) -> int:
    """Durable decision journal tooling (sched/journal.py):

        cli journal fsck     # per-segment integrity + the folded state
        cli journal show     # record stream (JSONL)
        cli journal compact  # fold completed lifecycles into one segment
    """
    from k8s_llm_scheduler_tpu.sched import journal as journal_mod

    root = args.dir or cfg.get("durability.journal_dir", None)
    if not root:
        raise SystemExit(
            "no journal: pass --dir DIR or set durability.journal_dir "
            "(DURABILITY_JOURNAL_DIR)"
        )
    if args.journal_cmd == "fsck":
        report = journal_mod.fsck(root)
        print(json.dumps(report, indent=1, sort_keys=True))
        # exit contract mirrors rollout fsck: 0 clean, 1 torn bytes found
        return 0 if report["ok"] else 1
    if args.journal_cmd == "show":
        n = 0
        for seg, rec in journal_mod.iter_records(root):
            print(json.dumps({"segment": seg, **rec}, sort_keys=True))
            n += 1
            if args.limit and n >= args.limit:
                break
        return 0
    # compact: open (replays + truncates any torn tail) and rotate. The
    # journal's single-writer flock refuses a directory a live
    # scheduler is writing — compacting under a live writer would
    # rotate its active segment out from underneath it.
    try:
        journal = journal_mod.DecisionJournal(root)
    except journal_mod.JournalError as exc:
        raise SystemExit(str(exc)) from exc
    try:
        stats = journal.compact()
    finally:
        journal.close()
    print(json.dumps(stats, sort_keys=True))
    return 0


def _rollout_registry(args: argparse.Namespace, cfg: Config):
    from k8s_llm_scheduler_tpu.rollout import CheckpointRegistry

    root = getattr(args, "registry", None) or cfg.get("rollout.registry_dir", None)
    if not root:
        raise SystemExit(
            "no registry: pass --registry DIR or set rollout.registry_dir "
            "(ROLLOUT_REGISTRY_DIR)"
        )
    return CheckpointRegistry(root)


def _retention_pins(cfg: Config) -> set:
    """Versions retention must keep beyond the keep-last window: every
    checkpoint an incident corpus mined against (learn.corpus_dir lineage
    — evicting one orphans the corpus provenance)."""
    import os as _os

    corpus_dir = cfg.get("learn.corpus_dir", None)
    if not corpus_dir or not _os.path.isdir(str(corpus_dir)):
        return set()
    from k8s_llm_scheduler_tpu.learn import IncidentCorpus

    return IncidentCorpus(corpus_dir).lineage_versions()


def _gate_from_cfg(cfg: Config, seed: int | None = None):
    from k8s_llm_scheduler_tpu.rollout import GateConfig

    g = cfg.section("rollout").get("gate", {})
    return GateConfig(
        seed=seed if seed is not None else int(g.get("seed", 0)),
        nodes=int(g.get("nodes", 12)),
        pods=int(g.get("pods", 48)),
        shapes=int(g.get("shapes", 8)),
        waves=int(g.get("waves", 2)),
        spread_tolerance=float(g.get("spread_tolerance", 0.02)),
        constraint_tolerance=float(g.get("constraint_tolerance", 0.0)),
        bound_tolerance=float(g.get("bound_tolerance", 0.0)),
    )


def cmd_rollout(args: argparse.Namespace, cfg: Config) -> int:
    """Live-rollout surface (rollout/): publish a trained checkpoint into
    the versioned registry, inspect/verify it, gate-and-promote a
    candidate, roll the active pointer back, or run the live watch loop
    (shadow scoring + canary controller) against a serving stack."""
    from k8s_llm_scheduler_tpu.rollout import run_gate  # noqa: F401 (lazy pkg import)

    registry = _rollout_registry(args, cfg)

    if args.rollout_cmd == "publish":
        from k8s_llm_scheduler_tpu.models.configs import get_config

        model = args.model or cfg.get("llm.model", "tiny")
        manifest = registry.publish(
            args.checkpoint,
            cfg=get_config(model),
            tokenizer=cfg.get("llm.tokenizer", "byte"),
            parent=args.parent,
            note=args.note,
        )
        retain = int(cfg.get("rollout.retain", 0))
        if retain:
            registry.retain(retain, pinned=_retention_pins(cfg))
        print(json.dumps({
            "metric": "rollout_publish",
            "version": manifest.version,
            "config": manifest.config_name,
            "fingerprint": manifest.config_fingerprint,
            "parent": manifest.parent,
            "n_files": len(manifest.files),
        }))
        return 0

    if args.rollout_cmd == "status":
        print(json.dumps(registry.status(), indent=1, sort_keys=True))
        return 0

    if args.rollout_cmd == "fsck":
        report = registry.fsck()
        bad = {v: p for v, p in report.items() if p}
        print(json.dumps({
            "metric": "rollout_fsck",
            "versions": len(report),
            "clean": len(report) - len(bad),
            "problems": {str(v): p for v, p in bad.items()},
        }, indent=1, sort_keys=True))
        return 1 if bad else 0

    if args.rollout_cmd == "rollback":
        active = registry.active()
        if active is None:
            print("no active version to roll back from", file=sys.stderr)
            return 2
        target = registry.get(active).parent
        if target is None:
            versions = [v for v in registry.versions() if v < active]
            target = versions[-1] if versions else None
        if target is None:
            print(f"active version {active} has no predecessor", file=sys.stderr)
            return 2
        registry.set_active(target)
        print(json.dumps({
            "metric": "rollout_rollback", "from": active, "to": target,
        }))
        return 0

    if args.rollout_cmd == "promote":
        return _rollout_promote(args, cfg, registry)

    if args.rollout_cmd == "watch":
        return _rollout_watch(args, cfg, registry)

    raise SystemExit(f"unknown rollout command {args.rollout_cmd!r}")


def _rollout_backend_factory(cfg: Config, checkpoint_path: str | None):
    """make() for a gate arm: the configured local stack serving
    `checkpoint_path` greedily (the arena's determinism contract)."""
    def make():
        from k8s_llm_scheduler_tpu.engine.local import build_local_backend

        return build_local_backend(**_backend_kwargs(
            cfg, temperature=0.0, checkpoint_path=checkpoint_path,
        ))

    return make


def _rollout_promote(args: argparse.Namespace, cfg: Config, registry) -> int:
    """Gate a candidate against the incumbent and move the active pointer.

    The incumbent arm serves the ACTIVE registry version (or the config's
    llm.checkpoint_path, or random-init when neither exists). In-process
    hot swapping of a separately-running scheduler is `rollout watch`'s
    job; promote moves the durable pointer that serving processes read at
    startup (and that watch controllers follow)."""
    from k8s_llm_scheduler_tpu.rollout import run_gate

    candidate = registry.get(args.version)
    if args.no_gate:
        registry.set_active(args.version)
        print(json.dumps({
            "metric": "rollout_promote", "version": args.version,
            "gate": "skipped",
        }))
        return 0
    active = registry.active()
    incumbent_ckpt = (
        str(registry.get(active).checkpoint_path)
        if active is not None
        else cfg.get("llm.checkpoint_path", None)
    )
    verdict = run_gate(
        _rollout_backend_factory(cfg, incumbent_ckpt),
        _rollout_backend_factory(cfg, str(candidate.checkpoint_path)),
        _gate_from_cfg(cfg, seed=args.seed),
    )
    registry.record_scores(args.version, {"gate": {
        "pass": verdict["pass"], "checks": verdict["checks"],
        "candidate": verdict["candidate"],
    }})
    if verdict["pass"]:
        registry.set_active(args.version)
    print(json.dumps({
        "metric": "rollout_promote",
        "version": args.version,
        "pass": verdict["pass"],
        "checks": verdict["checks"],
        "incumbent": verdict["incumbent"],
        "candidate": verdict["candidate"],
        "active": registry.active(),
    }))
    return 0 if verdict["pass"] else 1


def _rollout_watch(args: argparse.Namespace, cfg: Config, registry) -> int:
    """Live rollout loop: serve the active version, shadow-score the
    newest candidate, gate/promote/burn-in/rollback as new versions land.
    Runs until interrupted; /metrics (when enabled) exports the rollout
    gauges next to the scheduler stats."""
    import threading
    import time as _time

    from k8s_llm_scheduler_tpu.engine.local import build_local_backend
    from k8s_llm_scheduler_tpu.models.configs import get_config
    from k8s_llm_scheduler_tpu.rollout import (
        CanaryController,
        HotSwapper,
        ShadowScorer,
    )

    if cfg.get("llm.backend") == "stub":
        print("rollout watch needs llm.backend: local", file=sys.stderr)
        return 2

    active = registry.active()
    active_ckpt = (
        str(registry.get(active).checkpoint_path) if active is not None else None
    )
    model = cfg.get("llm.model", "tiny")
    backend = build_local_backend(**_backend_kwargs(
        cfg, checkpoint_path=active_ckpt or cfg.get("llm.checkpoint_path"),
    ))

    if args.fake_cluster:
        from k8s_llm_scheduler_tpu.testing import synthetic_cluster

        cluster = synthetic_cluster(args.fake_nodes)
    else:
        from k8s_llm_scheduler_tpu.cluster.kube import KubeCluster

        cluster = KubeCluster(
            watch_timeout_seconds=cfg.get("scheduler.watch_interval")
        )

    from k8s_llm_scheduler_tpu.core.breaker import CircuitBreaker
    from k8s_llm_scheduler_tpu.core.cache import DecisionCache
    from k8s_llm_scheduler_tpu.sched.client import DecisionClient
    from k8s_llm_scheduler_tpu.sched.loop import Scheduler

    cache = DecisionCache(
        ttl_seconds=cfg.get("cache.ttl_seconds"),
        max_size=cfg.get("cache.max_size"),
    )
    client = DecisionClient(
        backend, cache=cache, breaker=CircuitBreaker(),
        max_retries=cfg.get("llm.max_retries"),
        retry_delay=cfg.get("llm.retry_delay"),
        fallback_strategy=cfg.get("fallback.strategy"),
        fallback_enabled=cfg.get("fallback.enabled"),
    )
    scheduler = Scheduler(
        cluster, cluster, client,
        scheduler_name=cfg.get("scheduler.name"),
    )

    # SLO burn-rate engine over the serving stats: config.yaml documents
    # the `slo` block as a canary burn-in rollback input, so the watch
    # loop must build it too (not just `cli run`) — a latency regression
    # during an open burn-in then rolls back early instead of waiting for
    # the decision-count window to fill.
    from k8s_llm_scheduler_tpu.observability import slo as slo_mod

    slo_engine = slo_mod.from_config(cfg.section("slo"), scheduler.get_stats)
    if slo_engine is not None:
        slo_engine.on_trip.append(
            lambda name, _detail: client.breaker.slo_advisory(name)
        )
        if cfg.get("slo.brownout", True):
            # burn-rate brownout, both edges (see _run_scheduler)
            slo_engine.on_trip.append(
                lambda name, _d: client.enter_brownout(f"slo:{name}")
            )
            slo_engine.on_clear.append(
                lambda name, _d: client.exit_brownout(f"slo:{name}")
            )
        slo_engine.start(interval_s=float(cfg.get("slo.interval_s", 10.0)))

    swapper = HotSwapper(
        backend, registry, get_config(model),
        # restore onto the SERVING mesh with the serving quantization —
        # engine programs are compiled against that tree's shardings/dtypes
        mesh=backend.engine.mesh,
        quantize=cfg.get("llm.quantization"),
        cache=cache, mode=cfg.get("rollout.swap_mode", "auto"),
    )

    def incumbent_factory():
        # resolved at GATE time, not startup: after a promotion the next
        # candidate must be compared against the CURRENT active version,
        # or quality could ratchet back down to the startup checkpoint
        active_now = registry.active()
        ckpt = (
            str(registry.get(active_now).checkpoint_path)
            if active_now is not None
            else cfg.get("llm.checkpoint_path")
        )
        return _rollout_backend_factory(cfg, ckpt)()

    controller = CanaryController(
        registry, swapper,
        stats_provider=scheduler.get_stats,
        incumbent_factory=incumbent_factory,
        candidate_factory=lambda v: _rollout_backend_factory(
            cfg, str(registry.get(v).checkpoint_path)
        ),
        gate=_gate_from_cfg(cfg),
        burn_in_decisions=int(cfg.get("rollout.burn_in_decisions", 200)),
        trip_fallback_rate=float(cfg.get("rollout.trip_fallback_rate", 0.2)),
        trip_invalid_rate=float(cfg.get("rollout.trip_invalid_rate", 0.05)),
        trip_bind_failure_rate=float(
            cfg.get("rollout.trip_bind_failure_rate", 0.05)
        ),
        trip_decide_p99_ms=cfg.get("rollout.trip_decide_p99_ms", None),
        slo_engine=slo_engine,
    )
    shadow_frac = (
        args.shadow_frac
        if args.shadow_frac is not None
        else float(cfg.get("rollout.shadow_fraction", 0.0))
    )
    shadow = None

    def refresh_shadow():
        # Shadow the newest PROMOTABLE candidate: newer than the active
        # version and not gate/burn-in rejected. Anything else (an older
        # superseded version, a rejected one) would burn a whole resident
        # model's HBM scoring a policy that can never be promoted.
        nonlocal shadow
        active_now = registry.active() or 0
        versions = [
            v for v in registry.versions()
            if v > active_now and v not in controller.rejected
        ]
        if shadow_frac <= 0 or not versions:
            if shadow is not None:
                scheduler.shadow = None
                shadow.close()
                shadow.candidate.close()
                shadow = None
            return
        newest = versions[-1]
        if shadow is not None and shadow.candidate_version == newest:
            return
        if shadow is not None:
            scheduler.shadow = None
            shadow.close()
            shadow.candidate.close()
        shadow = ShadowScorer(
            build_local_backend(**_backend_kwargs(
                cfg, temperature=0.0,
                checkpoint_path=str(registry.get(newest).checkpoint_path),
            )),
            fraction=shadow_frac,
            candidate_version=newest,
        )
        scheduler.shadow = shadow

    stop = threading.Event()

    def controller_loop():
        poll = float(cfg.get("rollout.poll_seconds", 5.0))
        while not stop.wait(poll):
            try:
                refresh_shadow()
                controller.tick()
            except Exception:
                logger.exception("rollout controller tick failed")

    ctl_thread = threading.Thread(
        target=controller_loop, daemon=True, name="rollout-controller"
    )
    ctl_thread.start()

    metrics_server = None
    if cfg.get("metrics.enabled"):
        from k8s_llm_scheduler_tpu.observability.metrics import MetricsServer

        metrics_server = MetricsServer(
            lambda: {**scheduler.get_stats(), "rollout": controller.stats()},
            port=cfg.get("metrics.port"),
            is_alive=lambda: scheduler.running,
            slo_engine=slo_engine,
        )
        metrics_server.start()

    print(BANNER)
    logger.info(
        "rollout watch: registry=%s active=%s shadow_frac=%.3f",
        registry.root, registry.active(), shadow_frac,
    )

    async def _serve():
        task = asyncio.create_task(scheduler.run())
        try:
            await task
        except (KeyboardInterrupt, asyncio.CancelledError):
            scheduler.stop()
            close = getattr(cluster, "close", None)
            if close:
                close()
            await asyncio.wait_for(task, timeout=30)

    try:
        asyncio.run(_serve())
    except KeyboardInterrupt:
        pass
    finally:
        stop.set()
        ctl_thread.join(timeout=10)
        if slo_engine is not None:
            slo_engine.stop()
        if metrics_server:
            metrics_server.stop()
        if shadow is not None:
            shadow.close()
            shadow.candidate.close()
        backend.close()
        _time.sleep(0)  # graftlint: ok[raw-clock] — zero-length GIL yield for daemon teardown, not a paced wait
        print(json.dumps({
            **scheduler.get_stats(), "rollout": controller.stats(),
        }, indent=2, default=str))
    return 0


def _learn_corpus(args: argparse.Namespace, cfg: Config):
    from k8s_llm_scheduler_tpu.learn import IncidentCorpus

    root = getattr(args, "corpus", None) or cfg.get("learn.corpus_dir", None)
    if not root:
        raise SystemExit(
            "no incident corpus: pass --corpus DIR or set learn.corpus_dir "
            "(LEARN_CORPUS_DIR)"
        )
    return IncidentCorpus(root)


def _learn_config(args: argparse.Namespace, cfg: Config):
    from k8s_llm_scheduler_tpu.learn import LearnConfig

    sect = cfg.section("learn")
    seeds = getattr(args, "seeds", None)
    if seeds:
        mine_seeds = tuple(int(s) for s in seeds.split(",") if s.strip())
    else:
        mine_seeds = tuple(int(s) for s in sect.get("mine_seeds", [0, 1]))
    return LearnConfig(
        seed=int(getattr(args, "seed", 0) or 0),
        mine_seeds=mine_seeds,
        mine_nodes=int(sect.get("mine_nodes", 8)),
        mine_pods=int(sect.get("mine_pods", 48)),
        mine_waves=int(sect.get("mine_waves", 3)),
        spread_margin=float(sect.get("spread_margin", 0.005)),
        replay_fraction=float(
            getattr(args, "replay_fraction", None)
            if getattr(args, "replay_fraction", None) is not None
            else sect.get("replay_fraction", 0.3)
        ),
        steps=int(
            getattr(args, "steps", None) or sect.get("steps", 200)
        ),
        batch_size=int(sect.get("batch_size", 4)),
        seq_len=int(sect.get("seq_len", 1024)),
        lr=float(sect.get("lr", 3e-4)),
        weakness_cases=int(sect.get("weakness_cases", 32)),
        weakness_margin=float(sect.get("weakness_margin", 0.0)),
        gate=_gate_from_cfg(cfg),
        retain=int(sect.get("retain", 0)),
    )


def _learn_candidate_arm(cfg: Config, checkpoint_path: str | None):
    """The serving policy as a STACK arena arm for mining: the configured
    backend (stub, or the real engine serving `checkpoint_path` greedily
    — the arena determinism contract)."""
    from k8s_llm_scheduler_tpu.sim import ArmSpec

    if cfg.get("llm.backend") == "stub":
        from k8s_llm_scheduler_tpu.engine.backend import StubBackend

        return ArmSpec(name="llm", kind="stack", make=StubBackend)

    def make_llm():
        from k8s_llm_scheduler_tpu.engine.local import build_local_backend

        return build_local_backend(**_backend_kwargs(
            cfg, temperature=0.0, checkpoint_path=checkpoint_path,
        ))

    return ArmSpec(name="llm", kind="stack", make=make_llm)


def _learn_active_checkpoint(args, cfg: Config):
    """(registry | None, active version | None, checkpoint path | None) —
    the incumbent the loop mines, gates against, and finetunes from.
    The active VERSION is captured here, once, alongside the path: a
    promotion landing between this read and a later re-read would let
    corpus lineage point at a checkpoint that never produced the mined
    placements."""
    registry = None
    if getattr(args, "registry", None) or cfg.get("rollout.registry_dir", None):
        registry = _rollout_registry(args, cfg)
    active = registry.active() if registry is not None else None
    if active is not None:
        return registry, active, str(registry.get(active).checkpoint_path)
    return registry, None, cfg.get("llm.checkpoint_path", None)


def cmd_learn(args: argparse.Namespace, cfg: Config) -> int:
    """Closed policy-improvement loop (learn/): mine loss incidents from
    seeded arena runs of the serving policy vs the spread-lookahead
    teacher, build replay-mixed finetune batches, run the full
    mine -> finetune -> publish -> gate -> promote cycle, or inspect /
    replay its artifacts."""
    from k8s_llm_scheduler_tpu.learn import (
        curriculum_summary,
        mine_scenario,
        verify_learn_trace,
    )

    if args.learn_cmd == "replay":
        ok, detail = verify_learn_trace(args.trace)
        print(json.dumps({
            "metric": "learn_replay", "ok": ok, "trace": args.trace,
            "detail": detail,
        }))
        return 0 if ok else 1

    corpus = _learn_corpus(args, cfg)

    if args.learn_cmd == "status":
        status = corpus.status()
        if getattr(args, "registry", None) or cfg.get(
            "rollout.registry_dir", None
        ):
            registry = _rollout_registry(args, cfg)
            status["registry_active"] = registry.active()
            status["lineage_versions"] = sorted(corpus.lineage_versions())
        print(json.dumps(status, indent=1, sort_keys=True))
        return 0

    if args.learn_cmd == "mine":
        learn_cfg = _learn_config(args, cfg)
        _registry, active_version, ckpt = _learn_active_checkpoint(args, cfg)
        sources = [
            mine_scenario(
                spec, _learn_candidate_arm(cfg, ckpt),
                spread_margin=learn_cfg.spread_margin,
                wave_timeout_s=learn_cfg.gate.wave_timeout_s,
            )
            for spec in learn_cfg.mine_specs()
        ]
        record = corpus.add_version(
            sources,
            # the version captured WITH the checkpoint path, before the
            # (potentially minutes-long) mining pass — never a re-read
            checkpoint_version=active_version,
            note=args.note,
        )
        print(json.dumps({
            "metric": "learn_mine",
            "corpus_version": record["version"],
            "n_incidents": record["n_incidents"],
            "per_class": record["per_class"],
            "digest": record["digest"],
            "checkpoint_version": record["checkpoint_version"],
            "sources": len(sources),
        }))
        return 0

    if args.learn_cmd == "build":
        record = (
            corpus.get(args.version) if args.version else corpus.latest()
        )
        if record is None:
            print("corpus has no versions — run `cli learn mine` first",
                  file=sys.stderr)
            return 2
        learn_cfg = _learn_config(args, cfg)
        print(json.dumps({
            "metric": "learn_build",
            **curriculum_summary(record, learn_cfg.replay_fraction),
        }))
        return 0

    if args.learn_cmd == "run":
        return _learn_run(args, cfg, corpus)

    raise SystemExit(f"unknown learn command {args.learn_cmd!r}")


def _learn_run(args: argparse.Namespace, cfg: Config, corpus) -> int:
    """One full learn cycle against the configured local model: the
    production surface of learn/loop.LearnLoop."""
    from k8s_llm_scheduler_tpu.engine.tokenizer import build_builtin_tokenizer
    from k8s_llm_scheduler_tpu.learn import (
        LearnLoop,
        backend_decide,
        save_learn_trace,
    )
    from k8s_llm_scheduler_tpu.models.configs import get_config
    from k8s_llm_scheduler_tpu.rollout import run_gate

    if cfg.get("llm.backend") != "local":
        print("learn run needs llm.backend: local (finetuning requires the "
              "in-tree model)", file=sys.stderr)
        return 2
    if cfg.get("llm.tokenizer_path"):
        print("learn run finetunes with a builtin tokenizer; unset "
              "llm.tokenizer_path", file=sys.stderr)
        return 2
    registry = _rollout_registry(args, cfg)
    learn_cfg = _learn_config(args, cfg)
    tokenizer_name = cfg.get("llm.tokenizer", "byte")
    # the WIDENED serving config: the fingerprint the registry records
    # must match what restore/hot-swap will check against
    _tok, model_cfg = build_builtin_tokenizer(
        tokenizer_name, get_config(cfg.get("llm.model", "tiny"))
    )
    _registry2, _active, incumbent_ckpt = _learn_active_checkpoint(args, cfg)

    def backend_factory(checkpoint_path):
        from k8s_llm_scheduler_tpu.engine.local import build_local_backend

        return build_local_backend(**_backend_kwargs(
            cfg, temperature=0.0, checkpoint_path=checkpoint_path,
        ))

    def decide_factory(checkpoint_path):
        backend = backend_factory(checkpoint_path)
        return backend_decide(backend), backend.close

    loop = LearnLoop(
        registry, corpus, learn_cfg,
        mine_arm_factory=lambda: _learn_candidate_arm(cfg, incumbent_ckpt),
        incumbent_decide_factory=lambda: decide_factory(incumbent_ckpt),
        candidate_decide_factory=decide_factory,
        gate_runner=lambda version: run_gate(
            lambda: backend_factory(incumbent_ckpt),
            lambda: backend_factory(
                str(registry.get(version).checkpoint_path)
            ),
            learn_cfg.gate,
        ),
        model_cfg=model_cfg,
        tokenizer_name=tokenizer_name,
        answer_style=cfg.get("llm.answer_style", "direct"),
        mesh_axes=cfg.get("llm.mesh"),
    )

    metrics_server = None
    if cfg.get("metrics.enabled"):
        from k8s_llm_scheduler_tpu.observability.metrics import MetricsServer

        metrics_server = MetricsServer(
            lambda: {"learn": loop.stats()}, port=cfg.get("metrics.port"),
        )
        metrics_server.start()
    try:
        report = loop.run_cycle(args.work_dir, note=args.note)
    finally:
        if metrics_server is not None:
            metrics_server.stop()
    if args.trace:
        save_learn_trace(report, args.trace)
    print(json.dumps({
        "metric": "learn_run",
        "action": report["action"],
        "candidate_version": report["candidate_version"],
        "incumbent_version": report["incumbent_version"],
        "corpus_version": report["corpus_version"],
        "per_class": report["per_class"],
        "weakness_incumbent": report["weakness"]["incumbent"]["score"],
        "weakness_candidate": report["weakness"]["candidate"]["score"],
        "gate_pass": report["gate"]["pass"],
        "train_loss": report["train_loss"],
    }))
    return 0 if report["action"] == "promoted" else 1


def _debug_get(host: str, port: int, path: str, timeout: float = 5.0):
    import urllib.request

    url = f"http://{host}:{port}{path}"
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def _format_span_tree(node: dict, depth: int = 0) -> list[str]:
    dur = node.get("dur_ms")
    dur_txt = f"{dur:.2f}ms" if isinstance(dur, (int, float)) else "open"
    attrs = node.get("attrs") or {}
    attr_txt = (
        " " + " ".join(f"{k}={v}" for k, v in sorted(attrs.items()))
        if attrs else ""
    )
    status = "" if node.get("status", "ok") == "ok" else " [ERROR]"
    lines = [f"{'  ' * depth}{node['name']}  {dur_txt}{status}{attr_txt}"]
    for child in node.get("children", []):
        lines.extend(_format_span_tree(child, depth + 1))
    return lines


def cmd_trace(args: argparse.Namespace, cfg: Config) -> int:
    """Query a RUNNING scheduler's decision flight recorder over its
    metrics port (observability/spans.py; /debug/decisions + /debug/trace).

        cli trace list                 # newest decision traces
        cli trace show <trace-id>      # one trace's span tree
        cli trace tail                 # follow new traces as they complete
        cli trace export --out f.jsonl # dump the ring as JSONL (replayable
                                       # records, same shape as sim traces)
    """
    import time as _time
    import urllib.error

    from k8s_llm_scheduler_tpu.observability.spans import build_span_tree

    host = args.host
    port = args.port if args.port is not None else int(cfg.get("metrics.port"))

    def summarize(entry: dict) -> str:
        meta = entry.get("meta") or {}
        dur = entry.get("dur_ms")
        return (
            f"{entry['trace_id']:<16} {entry['name']:<10} "
            f"{(f'{dur:.1f}ms' if dur is not None else 'open'):>10} "
            f"{meta.get('source', '-'):<9} "
            f"{meta.get('selected_node', '-'):<20} "
            # fleet attribution (fleet/): which watch-space shard decided
            # this pod, and which cache tier answered (l1_hit/l2_hit/
            # miss/coalesced)
            f"{str(meta.get('shard_id', '-')):>5} "
            f"{meta.get('cache_tier', '-'):<9} "
            f"{meta.get('outcome', meta.get('fallback_reason', '-'))}"
        )

    try:
        if args.trace_cmd == "list":
            data = json.loads(_debug_get(
                host, port, f"/debug/decisions?n={args.n}"
            ))
            print(
                f"{'trace_id':<16} {'name':<10} {'duration':>10} "
                f"{'source':<9} {'node':<20} {'shard':>5} {'tier':<9} "
                f"outcome"
            )
            for entry in data["traces"]:
                print(summarize(entry))
            rec = data["recorder"]
            print(
                f"-- {rec['held']}/{rec['capacity']} held, "
                f"{rec['recorded']} recorded total"
            )
            return 0

        if args.trace_cmd == "show":
            try:
                body = _debug_get(
                    host, port, f"/debug/trace/{args.trace_id}"
                )
            except urllib.error.HTTPError as exc:
                if exc.code == 404:
                    print(
                        f"trace {args.trace_id!r} not found "
                        f"(ring may have evicted it)", file=sys.stderr,
                    )
                    return 1
                raise
            entry = json.loads(body)
            meta = entry.get("meta") or {}
            print(f"trace {entry['trace_id']}  meta={json.dumps(meta)}")
            for line in _format_span_tree(build_span_tree(entry["spans"])):
                print(line)
            return 0

        if args.trace_cmd == "tail":
            since = 0
            while True:
                data = json.loads(_debug_get(
                    host, port, f"/debug/decisions?n=1000&since={since}"
                ))
                for entry in data["traces"]:
                    print(summarize(entry), flush=True)
                    since = max(since, entry["seq"])
                _time.sleep(args.interval)  # graftlint: ok[raw-clock] — operator-facing tail interval; wall pacing is the product behavior

        if args.trace_cmd == "export":
            # /debug/export caps each response (EXPORT_MAX_BYTES) and ends
            # a capped body with a {"truncated": true, "next_cursor": N}
            # trailer line. The export file is documented as replayable
            # records, so follow the cursor until the ring is drained and
            # keep the trailer lines OUT of the output.
            lines: list[str] = []
            since = 0
            while True:
                body = _debug_get(
                    host, port, f"/debug/export?since={since}", timeout=30.0
                )
                chunk = [ln for ln in body.splitlines() if ln.strip()]
                trailer = None
                if chunk:
                    try:
                        last = json.loads(chunk[-1])
                    except ValueError:
                        last = None
                    if (
                        isinstance(last, dict)
                        and last.get("truncated") is True
                        and set(last) == {"truncated", "next_cursor"}
                    ):
                        trailer = last
                        chunk = chunk[:-1]
                lines.extend(chunk)
                if trailer is None:
                    break
                since = int(trailer["next_cursor"])
            out_body = "".join(line + "\n" for line in lines)
            if args.out:
                with open(args.out, "w", encoding="utf-8") as fh:  # graftlint: ok[nonatomic-state-write] — operator-requested trace export, not runtime state; a torn copy is re-runnable
                    fh.write(out_body)
                print(f"wrote {len(lines)} trace(s) to {args.out}")
            else:
                sys.stdout.write(out_body)
            return 0
    except KeyboardInterrupt:
        return 0
    except urllib.error.HTTPError as exc:
        # BEFORE OSError (HTTPError subclasses it): a server-side 500
        # carries the handler's exception text in its body — surface it
        # instead of misdiagnosing the endpoint as unreachable
        body = exc.read().decode(errors="replace").strip()
        print(
            f"metrics endpoint at {host}:{port} answered {exc.code}: "
            f"{body or exc.reason}",
            file=sys.stderr,
        )
        return 2
    except OSError as exc:
        print(
            f"cannot reach scheduler metrics endpoint at {host}:{port} "
            f"({exc}) — is it running with metrics.enabled?",
            file=sys.stderr,
        )
        return 2
    raise SystemExit(f"unknown trace command {args.trace_cmd!r}")


def cmd_fleet(args: argparse.Namespace, cfg: Config) -> int:
    """Fleet-scale serving tools (fleet/):

        cli fleet demo    # in-process sharded fleet over a fake cluster
        cli fleet shard <namespace/name>   # a pod's watch-space shard
    """
    from k8s_llm_scheduler_tpu.fleet import shard_of

    if args.fleet_cmd == "shard":
        n_shards = (
            args.n_shards if args.n_shards is not None
            else int(cfg.get("fleet.n_shards"))
        )
        if "/" in args.pod:
            namespace, name = args.pod.split("/", 1)
        else:
            namespace, name = "default", args.pod
        print(shard_of(namespace, name, n_shards))
        return 0

    if args.fleet_cmd == "demo":
        from k8s_llm_scheduler_tpu.engine.backend import StubBackend
        from k8s_llm_scheduler_tpu.fleet import Fleet
        from k8s_llm_scheduler_tpu.testing import (
            pod_burst,
            synthetic_cluster,
        )

        replicas = (
            args.replicas if args.replicas is not None
            else int(cfg.get("fleet.replicas"))
        )
        scheduler_name = cfg.get("scheduler.name")

        async def demo() -> dict:
            cluster = synthetic_cluster(args.nodes)
            for raw in pod_burst(
                args.pods, scheduler_name=scheduler_name,
                distinct_shapes=args.shapes,
            ):
                cluster.add_pod(raw)
            store = None
            lease_path = cfg.get("durability.lease_store_path", None)
            if lease_path:
                # durable lease backend (fleet/lease.FileLeaseStore):
                # same protocol, leases survive a demo restart
                from k8s_llm_scheduler_tpu.fleet import FileLeaseStore

                store = FileLeaseStore(
                    lease_path,
                    n_shards=int(cfg.get("fleet.n_shards")),
                    ttl_s=float(cfg.get("fleet.lease_ttl_s")),
                )
            kvplane = None
            if cfg.get("fleet.kvplane.enabled"):
                # shared prefix-KV plane: backends that pin prefixes
                # (LocalLLMBackend) join it via attach_kvplane; the
                # demo's StubBackend doesn't pin, so here the plane
                # only surfaces its gauges — real fleets deduplicate
                # snapshot prefill through it
                from k8s_llm_scheduler_tpu.fleet.kvplane import KVPlaneStore

                kvplane = KVPlaneStore(
                    fill_ttl_s=float(cfg.get("fleet.kvplane.fill_ttl_s")),
                    max_entries=int(cfg.get("fleet.kvplane.max_entries")),
                )
            fleet = Fleet(
                cluster, cluster, lambda i: StubBackend(),
                n_replicas=replicas,
                n_shards=int(cfg.get("fleet.n_shards")),
                scheduler_name=scheduler_name,
                lease_ttl_s=float(cfg.get("fleet.lease_ttl_s")),
                renew_interval_s=float(cfg.get("fleet.renew_interval_s")),
                l1_size=int(cfg.get("fleet.l1_size")),
                l2_size=int(cfg.get("fleet.l2_size")),
                list_pending=lambda: cluster.pending_pods(scheduler_name),
                store=store,
                kvplane=kvplane,
            )
            t0 = time.perf_counter()  # graftlint: ok[wall-clock-in-replay] — demo pacing/diagnostics printed to the operator, never serialized into a replay artifact
            await fleet.start()
            deadline = t0 + 60.0
            while time.perf_counter() < deadline:  # graftlint: ok[wall-clock-in-replay] — demo pacing/diagnostics printed to the operator, never serialized into a replay artifact
                if fleet.get_stats()["total_scheduled"] >= args.pods:
                    break
                await asyncio.sleep(0.02)
            wall_s = time.perf_counter() - t0  # graftlint: ok[wall-clock-in-replay] — demo pacing/diagnostics printed to the operator, never serialized into a replay artifact
            stats = fleet.get_stats()
            await fleet.stop()
            stats["wall_s"] = round(wall_s, 3)
            stats["decisions_per_s"] = round(
                stats["total_scheduled"] / wall_s, 1
            ) if wall_s else 0.0
            stats["bind_count"] = cluster.bind_count
            return stats

        stats = asyncio.run(demo())
        if args.json:
            print(json.dumps(stats))
            return 0
        print(
            f"fleet demo: {replicas} replica(s), {stats['n_shards']} shards, "
            f"{args.pods} pods over {args.nodes} nodes"
        )
        for r in stats["replicas"]:
            print(
                f"  replica-{r['replica_id']}: shards {r['owned_shards']}  "
                f"bound {r['total_scheduled']}  "
                f"(llm {r['llm_decisions']}, cache {r['cache_decisions']})  "
                f"fenced {r['fenced_binds']}"
            )
        l2 = stats["l2"]
        print(
            f"  shared L2: {l2['hits']} hits / {l2['misses']} misses "
            f"(generation {l2['generation']})"
        )
        print(
            f"  {stats['total_scheduled']} bound "
            f"({stats['decisions_per_s']}/s), "
            f"{stats['failed_bindings']} failed, "
            f"{stats['fenced_binds']} fenced; "
            f"cluster bind_count={stats['bind_count']}"
        )
        return 0 if stats["total_scheduled"] >= args.pods else 1

    if args.fleet_cmd == "kvplane":
        # Protocol demo of the shared prefix-KV plane: N replicas
        # (model-free StubPinEngines — KV is a pure function of the
        # token ids) pin a sequence of snapshot prefixes through one
        # KVPlaneStore. Shows the election/adopt/publish flow, the
        # generation bump, and the headline: fleet prefill tokens vs
        # what N independent replicas would have paid.
        from k8s_llm_scheduler_tpu.fleet.kvplane import (
            KVPlaneClient,
            KVPlaneStore,
            StubPinEngine,
        )

        replicas = (
            args.replicas if args.replicas is not None
            else int(cfg.get("fleet.replicas"))
        )
        kvstore = KVPlaneStore(
            fill_ttl_s=float(cfg.get("fleet.kvplane.fill_ttl_s")),
            max_entries=int(cfg.get("fleet.kvplane.max_entries")),
        )
        clients = [
            KVPlaneClient(
                kvstore, StubPinEngine(), replica=f"replica-{i}",
                wait_checks=int(cfg.get("fleet.kvplane.wait_checks")),
            )
            for i in range(replicas)
        ]
        for s in range(args.snapshots):
            ids = [7000 + s * 101 + j for j in range(args.pin_tokens)]
            for kc in clients:
                kc.pin(ids)
            if args.swap_every and (s + 1) % args.swap_every == 0:
                kvstore.bump_generation()
        fleet_prefill = sum(
            kc.engine.stats["prefill_tokens"] for kc in clients
        )
        solo_prefill = replicas * args.snapshots * args.pin_tokens
        out = {
            "replicas": replicas,
            "snapshots": args.snapshots,
            "pin_tokens": args.pin_tokens,
            "store": kvstore.gauges(),
            "clients": {kc.replica: kc.stats() for kc in clients},
            "fleet_prefill_tokens": fleet_prefill,
            "plane_off_prefill_tokens": solo_prefill,
            "dedup_ratio": round(solo_prefill / fleet_prefill, 2)
            if fleet_prefill else None,
        }
        if args.json:
            print(json.dumps(out))
            return 0
        g = out["store"]
        print(
            f"kvplane demo: {replicas} replica(s), {args.snapshots} "
            f"snapshot(s) x {args.pin_tokens} tokens"
        )
        print(
            f"  fills {g['fills']}  adoptions {g['adoptions']}  "
            f"generation {g['generation']}  entries {g['entries']}"
        )
        for kc in clients:
            st = kc.stats()
            print(
                f"  {kc.replica}: won {st['elections_won']}  "
                f"adopted {st['adoptions']}  "
                f"fallbacks {st['local_fallbacks']}  "
                f"shipped {st['bytes_shipped']}B"
            )
        print(
            f"  fleet prefill {fleet_prefill} tokens vs "
            f"{solo_prefill} plane-off "
            f"({out['dedup_ratio']}x dedup)"
        )
        return 0

    if args.fleet_cmd == "autoscale":
        from k8s_llm_scheduler_tpu.chaos.harness import (
            HashPlacementBackend,
            _VirtualClock,
        )
        from k8s_llm_scheduler_tpu.cluster.fake import FakeCluster, FakeNode
        from k8s_llm_scheduler_tpu.fleet import Fleet
        from k8s_llm_scheduler_tpu.fleet.autoscale import (
            AutoscaleConfig,
            AutoscaleController,
        )
        from k8s_llm_scheduler_tpu.fleet.lease import shard_of
        from k8s_llm_scheduler_tpu.sim.scenarios import (
            ScenarioSpec,
            generate_scenario,
        )

        # from_dict keeps its curated unknown-key error for config.yaml
        # typos; demo pacing then overrides the wall-clock cooldowns
        # (the virtual tick is one wave, so the config's second-scale
        # cooldowns would freeze the demo) while keeping their RATIO
        # (up fast, down deliberate) — the part the demo demonstrates
        acfg = dataclasses.replace(
            AutoscaleConfig.from_dict(cfg.section("autoscale")),
            up_cooldown_s=1.0, down_cooldown_s=3.0,
            join_budget_ticks=4, join_backoff_ticks=1,
            split_enabled=False,
        )
        scheduler_name = cfg.get("scheduler.name")
        spec = ScenarioSpec(
            name="autoscale-demo", seed=args.seed,
            n_nodes=args.nodes, n_pods=args.pods, shapes=16,
            arrival="diurnal", n_waves=args.waves,
            hetero=True, constraint_mix=("uniform",),
        )
        scenario = generate_scenario(spec)

        async def demo() -> dict:
            cluster = FakeCluster()
            for n in scenario.nodes:
                cluster.add_node(FakeNode(
                    name=n.name, cpu_capacity_cores=n.cpu_cores,
                    memory_capacity_gb=n.memory_gb, max_pods=n.max_pods,
                    labels=dict(n.labels), taints=n.taints, ready=n.ready,
                ))
            clock = _VirtualClock()
            fleet = Fleet(
                cluster, cluster, lambda i: HashPlacementBackend(),
                n_replicas=acfg.min_replicas,
                n_shards=2 * acfg.max_replicas,
                scheduler_name=scheduler_name,
                lease_ttl_s=6.0, clock=clock, snapshot_ttl_s=1e9,
                list_pending=lambda: cluster.pending_pods(scheduler_name),
            )
            wave_state = {"i": 0, "incoming": 0}
            controller = AutoscaleController(
                fleet, acfg,
                queue_depth_fn=lambda: wave_state["incoming"],
                clock=lambda: wave_state["i"] * 1.0,
            )

            def reoffer() -> list:
                pending = cluster.pending_pods(scheduler_name)
                coros = []
                for replica in fleet.replicas:
                    todo = [
                        p for p in pending
                        if replica.manager.owns(
                            shard_of(p.namespace, p.name, fleet.n_shards)
                        )
                    ]
                    coros.extend(
                        replica.scheduler.schedule_pod(p) for p in todo
                    )
                return coros

            trajectory = []
            await fleet.start(lease_threads=False)
            try:
                for wave_idx, wave in enumerate(scenario.waves):
                    clock.advance(1.0)
                    fleet.tick_leases()
                    wave_state["i"] = wave_idx + 1
                    wave_state["incoming"] = len(wave)
                    record = await controller.tick()
                    for pod in wave:
                        cluster.add_pod(pod.to_raw_pod())
                    # every demo pod is placeable (uniform constraints),
                    # so the wave drains exactly when nothing is pending
                    deadline = time.monotonic() + 30.0  # graftlint: ok[wall-clock-in-replay] — demo pacing/diagnostics printed to the operator, never serialized into a replay artifact
                    stalls = 0
                    while cluster.pending_pods(scheduler_name):
                        if time.monotonic() > deadline:  # graftlint: ok[wall-clock-in-replay] — demo pacing/diagnostics printed to the operator, never serialized into a replay artifact
                            break
                        await asyncio.sleep(0.01)
                        stalls += 1
                        if stalls % 25 == 0:
                            fleet.tick_leases()
                            coros = reoffer()
                            if coros:
                                await asyncio.gather(
                                    *coros, return_exceptions=True
                                )
                    trajectory.append({
                        "wave": wave_idx,
                        "pods": len(wave),
                        "replicas": fleet.n_live,
                        "pressure": record["pressure"],
                        "action": record["action"],
                    })
                stats = fleet.get_stats()
                return {
                    "trajectory": trajectory,
                    "scale_events": controller.scale_events(),
                    "autoscale": controller.stats(),
                    # the cluster's bind book is the authority: roster
                    # stats lose a drained replica's counts with it
                    "bind_count": cluster.bind_count,
                    "lease": stats["lease"],
                }
            finally:
                await fleet.stop()

        out = asyncio.run(demo())
        if args.json:
            print(json.dumps(out))
            return 0
        print(
            f"autoscale demo: {args.pods} pods over a {args.waves}-wave "
            f"diurnal curve, clamp [{acfg.min_replicas}, "
            f"{acfg.max_replicas}]"
        )
        for t in out["trajectory"]:
            bar = "#" * t["replicas"]
            print(
                f"  wave {t['wave']:>2}  pods {t['pods']:>4}  "
                f"pressure {t['pressure']:>6.2f}  replicas "
                f"{t['replicas']} {bar:<8} {t['action']}"
            )
        a = out["autoscale"]
        print(
            f"  {out['bind_count']}/{args.pods} bound exactly once; "
            f"{a['scale_ups']} up(s), {a['scale_downs']} down(s), "
            f"{a['join_failures']} failed join(s)"
        )
        return 0 if out["bind_count"] >= args.pods else 1

    if args.fleet_cmd == "top":
        from k8s_llm_scheduler_tpu.observability.fleetview import (
            FleetAggregator,
            render_top,
        )

        addrs = (
            [a for a in args.replicas.split(",") if a.strip()]
            if args.replicas
            else list(cfg.get("distributed.replica_addrs") or [])
        )
        if not addrs:
            print(
                "fleet top needs replica addresses (--replicas host:port,"
                "... or distributed.replica_addrs config)",
                file=sys.stderr,
            )
            return 2
        clients = _replica_clients(cfg, addrs, "--replicas")
        agg = FleetAggregator()
        for client in clients:
            agg.add_replica_client(client.addr, client)
        try:
            while True:
                round_info = agg.pull_all()
                if args.format == "prom":
                    print(agg.render_prometheus(), flush=True)
                else:
                    print(render_top(agg), flush=True)
                if args.once:
                    return 0 if round_info["ok"] else 2
                print()
                time.sleep(args.interval)  # graftlint: ok[raw-clock] — operator-facing watch interval; wall pacing is the product behavior
        except KeyboardInterrupt:
            return 0
        finally:
            for client in clients:
                client.close()

    raise SystemExit(f"unknown fleet command {args.fleet_cmd!r}")


def cmd_lint(args: argparse.Namespace, cfg: Config) -> int:
    """graftlint over the first-party tree (tools/graftlint): the AST
    concurrency, determinism, JAX-purity, protocol, and sharding rule
    families plus the py310 checks, with the framework's exit-code
    contract (0 clean / 1 findings / 2 usage error). `--rules` filters
    by rule id or family; `--changed [REF]` lints only files differing
    from REF (the pre-commit mode — the interprocedural graph still
    spans the whole tree); `--list-rules` prints the catalog grouped by
    family; `--format jsonl` emits one JSON object per finding for CI
    consumers."""
    repo_root = Path(__file__).resolve().parent.parent
    if str(repo_root) not in sys.path:
        # `tools` is a repo-root package, not part of the installed
        # k8s_llm_scheduler_tpu distribution
        sys.path.insert(0, str(repo_root))
    from tools.graftlint.__main__ import main as graftlint_main

    argv: list[str] = []
    if args.list_rules:
        argv.append("--list-rules")
    if args.rules:
        argv.extend(["--rules", args.rules])
    if args.changed is not None:
        argv.extend(["--changed", args.changed])
    if args.no_cache:
        argv.append("--no-cache")
    argv.extend(["--format", args.lint_format])
    argv.extend(args.paths)
    return graftlint_main(argv)


def cmd_complete(args: argparse.Namespace, cfg: Config) -> int:
    """Free-form generation through the PAGED continuous-batching path —
    the general-completion capability the reference gets from its remote
    chat_completion endpoint (reference scheduler.py:425-433), minus the
    network. Decision serving never uses this path (waves are strictly
    faster for bounded grammar decisions — engine/engine.py module doc);
    this command is its product surface: unbounded budgets, no grammar,
    long prompts via the chunked prefix path.

    The engine is SIZED FROM THE REQUEST: the prompt is read and encoded
    first, the page table is sized for (suffix + budget), and a prompt
    beyond the largest prefill bucket is installed as a chunked dense
    prefix (set_prefix) with only its tail going through bucketed suffix
    prefill — the same long-context machinery the 256-node cluster prompt
    uses."""
    from k8s_llm_scheduler_tpu.engine.local import build_local_backend
    from k8s_llm_scheduler_tpu.engine.tokenizer import (
        ByteTokenizer,
        HFTokenizerAdapter,
    )

    tokenizer_path = cfg.get("llm.tokenizer_path")
    tok = (
        HFTokenizerAdapter(tokenizer_path)
        if tokenizer_path
        else ByteTokenizer()
    )
    prompt = args.prompt if args.prompt is not None else sys.stdin.read()
    ids = (
        tok.chat_prompt("You are a helpful assistant.", prompt)
        if args.chat
        else tok.encode(prompt)
    )
    if not ids:
        print("empty prompt", file=sys.stderr)
        return 2

    page_size = int(cfg.get("llm.page_size"))
    buckets = tuple(cfg.get("llm.prefill_buckets"))
    # Long prompts: everything but a tail rides the chunked dense-prefix
    # path; the tail (and the decode budget) is what the page table must
    # hold per sequence. Split at the LARGEST bucket — only prompts beyond
    # it need the long-context machinery; everything shorter is one
    # ordinary bucketed suffix prefill (splitting at the smallest bucket
    # forced set_prefix's chunked path on nearly every completion).
    tail = min(len(ids), max(1, buckets[-1]))
    pages_needed = -(-(tail + args.max_new_tokens + 1) // page_size) + 1
    overrides = dict(
        model=args.model or cfg.get("llm.model", "tiny"),
        max_new_tokens=args.max_new_tokens,
        max_pages_per_seq=pages_needed,
        num_pages=max(512, pages_needed + 8),
        constrained=False,
    )
    if args.temperature is not None:
        overrides["temperature"] = args.temperature
    if getattr(args, "spec", False):
        overrides["spec_enabled"] = True
    backend = build_local_backend(**_backend_kwargs(cfg, **overrides))
    try:
        from k8s_llm_scheduler_tpu.observability import spans

        engine = backend.engine
        # Trace the completion: generate() runs on THIS thread, so the
        # engine's ambient spans (prefix_prefill for the chunked long-
        # prompt path, prefill_dispatch, per-chunk decode_chunk, and
        # spec_decode accept/reject when --spec) land in one flight-
        # recorder trace — the paged path's answer to the decision
        # traces the scheduler records.
        with spans.start_trace(
            "completion", layer="engine", prompt_tokens=len(ids), spec=bool(
                getattr(args, "spec", False)
            ),
        ) as trace:
            if len(ids) > tail:
                engine.set_prefix(ids[:-tail])
            fin = engine.generate(
                ids[-tail:], max_new_tokens=args.max_new_tokens
            )
            if trace is not None:
                trace.set_meta(generated_tokens=len(fin.token_ids))
        print(fin.text)
        logger.info(
            "completed %d tokens in %.1f ms%s", len(fin.token_ids),
            fin.latency_ms,
            f" (trace {trace.trace_id})" if trace is not None else "",
        )
        return 0
    finally:
        backend.close()


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="k8s_llm_scheduler_tpu")
    parser.add_argument("--config", default=None, help="path to config.yaml")
    sub = parser.add_subparsers(dest="command", required=True)

    p_run = sub.add_parser("run", help="run the scheduler against a cluster")
    p_run.add_argument("--fake-cluster", action="store_true")
    p_run.add_argument("--fake-nodes", type=int, default=3)

    p_demo = sub.add_parser("demo", help="schedule fixture pods on a fake cluster")
    p_demo.add_argument("--fake-nodes", type=int, default=3)

    p_verify = sub.add_parser("verify", help="preflight environment checks")
    p_verify.add_argument("--fast", action="store_true", help="skip model smoke test")

    p_bench = sub.add_parser("bench", help="run the benchmark")
    p_bench.add_argument("bench_args", nargs="*")

    p_train = sub.add_parser(
        "train", help="fine-tune the decision model; save an orbax checkpoint"
    )
    p_train.add_argument("--out", required=True, help="checkpoint output dir")
    p_train.add_argument("--steps", type=int, default=20)
    p_train.add_argument("--batch-size", type=int, default=4)
    p_train.add_argument("--seq-len", type=int, default=2048)
    p_train.add_argument(
        "--model", default="tiny",
        help="config name (default tiny — bootstrap distillation targets "
             "small configs; pass llm.model sizes deliberately)",
    )

    p_train.add_argument("--lr", type=float, default=3e-4)
    p_train.add_argument(
        "--lr-schedule", default="constant", choices=("constant", "cosine"),
    )
    p_train.add_argument(
        "--name-weight", type=float, default=8.0,
        help="loss upweight on the selected_node value tokens (the one "
             "decision-bearing span of the answer)",
    )
    p_train.add_argument(
        "--cot-weight", type=float, default=1.0,
        help="loss weight on the CoT score tokens (answer_style=cot); the "
             "argmax digit and name always carry --name-weight",
    )
    p_train.add_argument(
        "--micro-frac", type=float, default=0.0,
        help="fraction of batch rows replaced by bare argmax drills "
             "(answer_style=cot; train-only scaffolding)",
    )
    p_train.add_argument(
        "--placement-frac", type=float, default=0.0,
        help="fraction of cases drawn from sequential-placement rollouts "
             "(the fold manifold eval_placement walks; train/distill.py)",
    )
    p_train.add_argument(
        "--diverse-frac", type=float, default=0.0,
        help="fraction of cases drawn from constraint scenarios (hetero "
             "SKUs, taints, selectors, affinity) at train-disjoint seeds",
    )
    p_train.add_argument(
        "--prompt-lm-frac", type=float, default=0.0,
        help="fraction of rows trained with plain full-sequence LM loss "
             "(induction-head pressure from the repetitive prompt text; "
             "the echo/retrieval circuit needs it — train/distill.py)",
    )
    p_train.add_argument(
        "--probe-every", type=int, default=0,
        help="log greedy held-out teacher agreement every N steps (0=off)",
    )
    p_train.add_argument(
        "--save-every", type=int, default=0,
        help="snapshot the checkpoint every N steps (0=only at the end)",
    )
    p_train.add_argument(
        "--resume", action="store_true",
        help="resume params from --out's latest snapshot if present",
    )
    p_train.add_argument(
        "--seed", type=int, default=0,
        help="init + data-stream seed; vary it on resumed continuations "
             "so the stream does not replay from the start",
    )
    p_train.add_argument(
        "--easy-frac", type=float, default=0.0,
        help="fraction of curriculum (wide-margin) cases mixed into the "
             "teacher stream (train-only; eval never draws from it)",
    )
    p_train.add_argument(
        "--eval", action="store_true",
        help="after training, report teacher agreement + placement quality "
             "for the saved checkpoint",
    )
    p_train.add_argument("--eval-cases", type=int, default=64)
    p_train.add_argument(
        "--registry", default=None,
        help="publish the finished checkpoint into this rollout registry "
             "(default: rollout.registry_dir when configured; lineage + "
             "train scores land in the manifest)",
    )
    p_train.add_argument(
        "--no-publish", action="store_true",
        help="skip registry publication even when a registry is configured "
             "(bare orbax dir only — the back-compat path)",
    )

    p_eval = sub.add_parser(
        "eval",
        help="decision-quality report: teacher agreement + placement spread",
    )
    p_eval.add_argument(
        "--checkpoint", default=None,
        help="orbax/safetensors checkpoint dir (default: random-init floor)",
    )
    p_eval.add_argument("--model", default=None, help="config name")
    p_eval.add_argument("--cases", type=int, default=64)
    p_eval.add_argument(
        "--temperature", type=float, default=0.0,
        help="eval-time sampling temperature (default 0.0 = greedy, the "
             "deterministic report card; serving keeps llm.temperature)",
    )
    p_eval.add_argument("--placement-pods", type=int, default=32)
    p_eval.add_argument(
        "--scenarios", action="store_true",
        help="add the per-scenario-class agreement table (heterogeneous "
             "capacities, taints, selectors, affinity)",
    )
    p_eval.add_argument("--scenario-cases", type=int, default=32)

    p_sim = sub.add_parser(
        "sim",
        help="cluster-twin scenario arena: seeded workloads through the "
             "real stack, scored across decision arms (sim/)",
    )
    p_sim.add_argument("--name", default="scenario")
    p_sim.add_argument("--seed", type=int, default=0)
    p_sim.add_argument("--nodes", type=int, default=16)
    p_sim.add_argument("--pods", type=int, default=64)
    p_sim.add_argument("--shapes", type=int, default=8)
    p_sim.add_argument(
        "--arrival", choices=("burst", "poisson", "waves"), default="burst",
    )
    p_sim.add_argument(
        "--arrival-rate", type=float, default=500.0,
        help="pods/sec for --arrival poisson",
    )
    p_sim.add_argument(
        "--waves", type=int, default=4,
        help="wave count for --arrival waves",
    )
    p_sim.add_argument(
        "--homogeneous", action="store_true",
        help="uniform node SKUs (default: heterogeneous ladder)",
    )
    p_sim.add_argument("--taint-frac", type=float, default=0.0)
    p_sim.add_argument(
        "--constraints", default="uniform",
        help="comma list of scenario classes cycled over pod shapes "
             "(train/eval.SCENARIO_CLASSES: uniform, hetero-capacity, "
             "tainted, selector, affinity)",
    )
    p_sim.add_argument(
        "--churn", action="append", default=None, metavar="WAVE:KIND:NODE",
        help="node churn applied before WAVE (kind: fail|recover|add|"
             "delete); repeatable",
    )
    p_sim.add_argument(
        "--arms",
        default="stub,resource_balanced,least_loaded,round_robin,teacher",
        help="comma list: llm (configured backend, greedy), stub, teacher, "
             "or any core/fallback strategy",
    )
    p_sim.add_argument("--trace", default=None, help="record trace here")
    p_sim.add_argument(
        "--replay", default=None,
        help="verify a recorded trace replays bit-identically, then exit",
    )
    p_sim.add_argument("--out", default=None, help="full JSON report path")
    p_sim.add_argument("--wave-timeout", type=float, default=300.0)
    p_sim.add_argument(
        "--metrics-port", type=int, default=None,
        help="serve live arena scores on /metrics while running",
    )

    p_chaos = sub.add_parser(
        "chaos",
        help="deterministic chaos plane: seeded fault schedules through "
             "the real stack, invariant-monitored, replayable (chaos/)",
    )
    csub = p_chaos.add_subparsers(dest="chaos_cmd", required=True)
    p_clist = csub.add_parser("list", help="list regimes")  # noqa: F841
    p_crun = csub.add_parser(
        "run", help="run one regime (or all) and print the verdict",
    )
    p_crun.add_argument(
        "--regime", default="all",
        help="regime name (`cli chaos list`) or 'all'",
    )
    p_crun.add_argument("--seed", type=int, default=0)
    p_crun.add_argument("--waves", type=int, default=8)
    p_crun.add_argument("--nodes", type=int, default=12)
    p_crun.add_argument(
        "--pods", type=int, default=None,
        help="default: 96 (single/wire regimes) or 64 (fleet regimes)",
    )
    p_crun.add_argument("--wave-timeout", type=float, default=30.0)
    p_crun.add_argument(
        "--deadline-ms", type=float, default=2000.0,
        help="per-decision deadline budget riding every frame (<=0 "
             "disables; loose by default — tight wall-clock deadlines "
             "would break run-to-run placement determinism)",
    )
    p_crun.add_argument(
        "--trace", default=None,
        help="record the (single) regime's replayable trace here",
    )
    p_crun.add_argument("--out", default=None, help="full JSON report path")
    p_creplay = csub.add_parser(
        "replay", help="verify a recorded chaos trace replays byte-identically",
    )
    p_creplay.add_argument("trace", help="trace file from `chaos run --trace`")

    p_journal = sub.add_parser(
        "journal",
        help="durable decision journal: fsck/show/compact "
             "(sched/journal.py; durability.* config block)",
    )
    jsub = p_journal.add_subparsers(dest="journal_cmd", required=True)
    for name, help_text in (
        ("fsck", "per-segment integrity report + the folded end state"),
        ("show", "dump the record stream as JSONL"),
        ("compact", "fold completed lifecycles into one fresh segment"),
    ):
        p_j = jsub.add_parser(name, help=help_text)
        p_j.add_argument(
            "--dir", default=None,
            help="journal directory (default: durability.journal_dir)",
        )
        if name == "show":
            p_j.add_argument(
                "--limit", type=int, default=0,
                help="stop after N records (0 = all)",
            )

    p_rollout = sub.add_parser(
        "rollout",
        help="live policy rollout: checkpoint registry, canary gate, "
             "shadow scoring, hot weight swap (rollout/)",
    )
    rsub = p_rollout.add_subparsers(dest="rollout_cmd", required=True)

    def _with_registry(p):
        p.add_argument(
            "--registry", default=None,
            help="registry dir (default: rollout.registry_dir / "
                 "ROLLOUT_REGISTRY_DIR)",
        )
        return p

    p_publish = _with_registry(rsub.add_parser(
        "publish", help="register a trained checkpoint as a new version"
    ))
    p_publish.add_argument(
        "--checkpoint", required=True,
        help="orbax checkpoint dir (train/distill.train_and_save output)",
    )
    p_publish.add_argument(
        "--model", default=None,
        help="config name the checkpoint is shaped for (default llm.model; "
             "stamps the fingerprint hot-swap compatibility is checked "
             "against)",
    )
    p_publish.add_argument("--parent", type=int, default=None)
    p_publish.add_argument("--note", default="")

    _with_registry(rsub.add_parser(
        "status", help="list versions, scores, and the active pointer"
    ))
    _with_registry(rsub.add_parser(
        "fsck", help="digest-verify every version (exit 1 on any damage)"
    ))
    _with_registry(rsub.add_parser(
        "rollback", help="move the active pointer back to its parent"
    ))

    p_promote = _with_registry(rsub.add_parser(
        "promote",
        help="arena-gate a candidate vs the incumbent; set active on pass",
    ))
    p_promote.add_argument("--version", type=int, required=True)
    p_promote.add_argument("--seed", type=int, default=None,
                           help="gate scenario seed (default rollout.gate.seed)")
    p_promote.add_argument(
        "--no-gate", action="store_true",
        help="skip the arena gate (set active unconditionally)",
    )

    p_watch = _with_registry(rsub.add_parser(
        "watch",
        help="serve the active version and run the live canary loop "
             "(shadow scoring, gate-promote, burn-in auto-rollback)",
    ))
    p_watch.add_argument(
        "--shadow-frac", type=float, default=None,
        help="fraction of live decisions mirrored through the newest "
             "candidate (default rollout.shadow_fraction)",
    )
    p_watch.add_argument("--fake-cluster", action="store_true")
    p_watch.add_argument("--fake-nodes", type=int, default=3)

    p_trace = sub.add_parser(
        "trace",
        help="decision flight recorder: list/show/tail/export traces from "
             "a running scheduler's /debug endpoints (observability/)",
    )
    tsub = p_trace.add_subparsers(dest="trace_cmd", required=True)

    def _with_endpoint(p):
        p.add_argument("--host", default="127.0.0.1")
        p.add_argument(
            "--port", type=int, default=None,
            help="metrics port (default metrics.port from config)",
        )
        return p

    p_tlist = _with_endpoint(tsub.add_parser(
        "list", help="newest decision traces (summary lines)"
    ))
    p_tlist.add_argument("-n", type=int, default=20)
    p_tshow = _with_endpoint(tsub.add_parser(
        "show", help="one trace's full span tree"
    ))
    p_tshow.add_argument("trace_id")
    p_ttail = _with_endpoint(tsub.add_parser(
        "tail", help="follow new traces as they complete (Ctrl-C to stop)"
    ))
    p_ttail.add_argument("--interval", type=float, default=1.0)
    p_texport = _with_endpoint(tsub.add_parser(
        "export",
        help="dump the ring as JSONL (one canonical-JSON trace per line, "
             "replayable alongside sim traces)",
    ))
    p_texport.add_argument("--out", default=None, help="file (default stdout)")

    p_lint = sub.add_parser(
        "lint",
        help="graftlint: AST concurrency & JAX-purity analyzer + py310 "
             "checks over the first-party tree (tools/graftlint)",
    )
    p_lint.add_argument(
        "--rules", default=None,
        help="comma-separated rule ids or families (concurrency, "
             "determinism, jax, protocol, py310, sharding); default: all",
    )
    p_lint.add_argument(
        "--format", choices=("human", "jsonl"), default="human",
        dest="lint_format",
    )
    p_lint.add_argument(
        "--list-rules", action="store_true",
        help="print the rule catalog grouped by family",
    )
    p_lint.add_argument(
        "--changed", nargs="?", const="HEAD", default=None, metavar="REF",
        help="lint only first-party files differing from REF (default "
             "HEAD) plus untracked ones — the pre-commit mode",
    )
    p_lint.add_argument(
        "--no-cache", action="store_true",
        help="ignore and do not write the on-disk analysis cache",
    )
    p_lint.add_argument(
        "paths", nargs="*",
        help="files to lint (default: the whole first-party tree)",
    )

    p_fleet = sub.add_parser(
        "fleet",
        help="fleet-scale serving (fleet/): sharded-replica demo + shard "
             "mapping",
    )
    fsub = p_fleet.add_subparsers(dest="fleet_cmd", required=True)
    p_fdemo = fsub.add_parser(
        "demo",
        help="run an in-process sharded fleet over a fake cluster and "
             "print shard ownership, decision mix, and tier hits",
    )
    p_fdemo.add_argument(
        "--replicas", type=int, default=None,
        help="scheduler replicas (default: fleet.replicas config)",
    )
    p_fdemo.add_argument("--pods", type=int, default=200)
    p_fdemo.add_argument("--nodes", type=int, default=12)
    p_fdemo.add_argument(
        "--shapes", type=int, default=16,
        help="distinct pod resource shapes (cache-coherence groups)",
    )
    p_fdemo.add_argument("--json", action="store_true")
    p_fkv = fsub.add_parser(
        "kvplane",
        help="shared prefix-KV plane demo (fleet/kvplane/): N replicas "
             "pin snapshot prefixes through one store — shows the "
             "fill-once/adopt-everywhere flow and the prefill dedup "
             "ratio vs independent replicas",
    )
    p_fkv.add_argument(
        "--replicas", type=int, default=None,
        help="plane clients (default: fleet.replicas config)",
    )
    p_fkv.add_argument(
        "--snapshots", type=int, default=4,
        help="distinct snapshot prefixes pinned in sequence",
    )
    p_fkv.add_argument(
        "--pin-tokens", type=int, default=512,
        help="tokens per snapshot prefix",
    )
    p_fkv.add_argument(
        "--swap-every", type=int, default=0,
        help="bump the plane generation every N snapshots (0 = never) — "
             "the hot-swap invalidation path",
    )
    p_fkv.add_argument("--json", action="store_true")
    p_fshard = fsub.add_parser(
        "shard", help="print a pod's watch-space shard id"
    )
    p_fshard.add_argument(
        "pod", help="namespace/name (bare name = default namespace)"
    )
    p_fshard.add_argument(
        "--n-shards", type=int, default=None,
        help="shard count (default: fleet.n_shards config)",
    )
    p_fauto = fsub.add_parser(
        "autoscale",
        help="elastic-fleet demo: replay a seeded diurnal arrival curve "
             "through the SLO-burn-driven autoscale controller "
             "(fleet/autoscale.py) over a fake cluster and print the "
             "replica trajectory + scale events",
    )
    p_fauto.add_argument("--pods", type=int, default=240)
    p_fauto.add_argument("--nodes", type=int, default=24)
    p_fauto.add_argument(
        "--waves", type=int, default=12,
        help="diurnal curve length in waves (one controller tick each)",
    )
    p_fauto.add_argument("--seed", type=int, default=0)
    p_fauto.add_argument("--json", action="store_true")
    p_ftop = fsub.add_parser(
        "top",
        help="live merged fleet telemetry: pull every replica's stats/"
             "trace slices over the wire (telemetry_pull) and render one "
             "fleet-wide view with merged-bucket percentiles",
    )
    p_ftop.add_argument(
        "--replicas", default=None,
        help="comma-separated replica addrs host:port (default: "
             "distributed.replica_addrs config)",
    )
    p_ftop.add_argument(
        "--interval", type=float, default=2.0,
        help="refresh period in seconds",
    )
    p_ftop.add_argument(
        "--once", action="store_true",
        help="render one frame and exit (scripting/tests)",
    )
    p_ftop.add_argument(
        "--format", choices=("text", "prom"), default="text",
        help="text frame or one merged Prometheus exposition",
    )

    p_learn = sub.add_parser(
        "learn",
        help="closed policy-improvement loop (learn/): mine loss "
             "incidents, build finetune curricula, run the full "
             "mine->finetune->gate->promote cycle",
    )
    lsub = p_learn.add_subparsers(dest="learn_cmd", required=True)

    def _with_corpus(p):
        p.add_argument(
            "--corpus", default=None,
            help="incident corpus dir (default: learn.corpus_dir / "
                 "LEARN_CORPUS_DIR)",
        )
        return p

    p_lmine = _with_registry(_with_corpus(lsub.add_parser(
        "mine",
        help="run the serving policy vs the teacher over seeded arena "
             "scenarios and write a new incident-corpus version",
    )))
    p_lmine.add_argument(
        "--seeds", default=None,
        help="comma-separated mining scenario seeds (default: "
             "learn.mine_seeds)",
    )
    p_lmine.add_argument("--note", default="")
    p_lbuild = _with_corpus(lsub.add_parser(
        "build",
        help="reconstruct a corpus version into curriculum cases and "
             "print the batch mix (dry-run of the finetune input)",
    ))
    p_lbuild.add_argument("--version", type=int, default=None)
    p_lbuild.add_argument("--replay-fraction", type=float, default=None)
    p_lrun = _with_registry(_with_corpus(lsub.add_parser(
        "run",
        help="one full learn cycle: mine -> finetune -> publish -> "
             "two-sided gate -> promote (exit 1 when rejected)",
    )))
    p_lrun.add_argument("--seed", type=int, default=0)
    p_lrun.add_argument("--seeds", default=None,
                        help="mining scenario seeds (default learn.mine_seeds)")
    p_lrun.add_argument("--steps", type=int, default=None)
    p_lrun.add_argument("--replay-fraction", type=float, default=None)
    p_lrun.add_argument(
        "--work-dir", default="learn-work",
        help="cycle working dir (candidate checkpoint lands here before "
             "publish)",
    )
    p_lrun.add_argument(
        "--trace", default=None,
        help="record the cycle's byte-replayable learn trace here",
    )
    p_lrun.add_argument("--note", default="")
    _with_registry(_with_corpus(lsub.add_parser(
        "status", help="corpus versions, per-class counts, lineage",
    )))
    p_lreplay = lsub.add_parser(
        "replay",
        help="verify a recorded learn trace replays byte-identically",
    )
    p_lreplay.add_argument("trace", help="trace file from `learn run --trace`")

    p_complete = sub.add_parser(
        "complete",
        help="free-form text completion (paged continuous-batching path)",
    )
    p_complete.add_argument(
        "--prompt", default=None, help="prompt text (default: stdin)"
    )
    p_complete.add_argument("--model", default=None, help="config name")
    p_complete.add_argument("--max-new-tokens", type=int, default=200)
    p_complete.add_argument("--temperature", type=float, default=None)
    p_complete.add_argument(
        "--chat", action="store_true",
        help="wrap the prompt in the chat template",
    )
    p_complete.add_argument(
        "--spec", action="store_true",
        help="speculative decoding: distilled-draft propose, target verify "
             "(llm.spec_* config keys pick the draft and K)",
    )

    args = parser.parse_args(argv)
    cfg = load_config(yaml_path=args.config)
    setup_logging(
        level=cfg.get("logging.level"),
        fmt=cfg.get("logging.format"),
        file=cfg.get("logging.file"),
    )
    # Apply the observability block ONCE for every command: tracing on/off
    # and the flight-recorder ring size are process-global (spans.py), the
    # same way logging is.
    from k8s_llm_scheduler_tpu.observability import spans

    spans.configure(
        enabled=bool(cfg.get("observability.tracing", True)),
        capacity=int(cfg.get("observability.flight_recorder_size", 256)),
    )
    handlers = {
        "run": cmd_run,
        "demo": cmd_demo,
        "verify": cmd_verify,
        "bench": cmd_bench,
        "train": cmd_train,
        "eval": cmd_eval,
        "sim": cmd_sim,
        "chaos": cmd_chaos,
        "journal": cmd_journal,
        "rollout": cmd_rollout,
        "learn": cmd_learn,
        "fleet": cmd_fleet,
        "trace": cmd_trace,
        "lint": cmd_lint,
        "complete": cmd_complete,
    }
    return handlers[args.command](args, cfg)


if __name__ == "__main__":
    raise SystemExit(main())
