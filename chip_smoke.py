"""chip_smoke.py — the quickest proof that the scheduler still starts on the chip.

Drives the main path once, on ONE TPU chip, in ONE process, at the full
width of llama-3.2-1b-instruct (16 layers, d_model 2048, vocab 128,256;
random weights from a seed, no checkpoint, no network):

    pending pod -> snapshot -> prompt -> in-tree Llama engine
                -> validated decision -> bind

through the entry points `cli run --fake-cluster` uses: `cli._build_stack`
over `synthetic_cluster(32)`, then `Scheduler.run()` over a 48-pod burst of
24 distinct shapes, every config key at its config.py default except the
model name and the committed 4k-BPE tokenizer fixture.

    python3 chip_smoke.py

Exit 0 and, as the LAST stdout line,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`
(the line before it is the full summary) — only if JAX found a TPU, every
pod was bound, every decision that missed the cache came from the model
(none from the heuristic fallback ladder, none timed out, the breaker
never opened), no compile or prewarm failed, `auto` attention resolved to
the Mosaic-compiled Pallas kernels at every traced call site, and the
cascade forward agrees with the plain full-attention forward on a small
input. Anything else: a non-zero exit, the reasons on stderr, no result
line. The fallback ladder is product safety code — a dead engine still
binds every pod — which is exactly why this looks at the SOURCE of each
decision.
"""

from __future__ import annotations

import asyncio
import copy
import functools
import importlib.metadata
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

MODEL = "llama-3.2-1b-instruct"
NODES, PODS, SHAPES = 32, 48, 24  # >= 24 cache misses: three waves at max_batch 8
DEADLINE_S = 1100.0  # the chip check allows 1200 s, compilation included
# Logit agreement bound for the reference phase, set from the dtype: bf16
# rounds at 2^-8 and the two forwards reorder 16 layers of accumulation.
LOGIT_TOL = 16 * 2.0**-8


def smoke_config(model: str = MODEL, bpe_fixture: bool = True):
    """config.py DEFAULTS (not the CWD's config.yaml, not the environment)
    with the model named and the committed 4k-BPE tokenizer fixture (as
    bench.py serves every preset; off = the builtin byte tokenizer, for
    configs whose vocab is smaller than the fixture's)."""
    from k8s_llm_scheduler_tpu.config import DEFAULTS, Config
    from k8s_llm_scheduler_tpu.testing import BPE_FIXTURE

    cfg = Config(copy.deepcopy(DEFAULTS))
    cfg.data["llm"]["model"] = model
    cfg.data["llm"]["tokenizer_path"] = BPE_FIXTURE if bpe_fixture else None
    return cfg


class _Takes:
    """What the process built in each phase: deltas of the program's own
    compile log (utils/compile_cache.py `COMPILE_LOG`, whose listeners
    build_local_backend registers before its first jit)."""

    def __init__(self) -> None:
        from k8s_llm_scheduler_tpu.utils.compile_cache import COMPILE_LOG

        self.log = COMPILE_LOG.install()
        self.mark = self.log.books()

    def take(self) -> dict:
        """Books since the last take(), and how many programs of them took
        a second or more to load or compile."""
        now = self.log.books()
        out = {k: round(now[k] - self.mark[k], 3) for k in now}
        out["programs_over_1s"] = sum(
            p.load_compile_s >= 1.0
            for p in self.log.programs[self.mark["programs"]:now["programs"]]
        )
        self.mark = now
        return out


def _warm_up(backend, nodes, pods, deadline: float) -> None:
    """Set-up: compile what the burst will dispatch, through the backend
    seam the decision client calls — prefix + grammar install, one
    full-width wave, then the idle worker's half-width sibling prewarm.
    Nothing is retried: a decision that fails or times out here raises
    with the backend's message and the run ends."""
    backend.prewarm_prefix(nodes).result(timeout=deadline - time.monotonic())
    with ThreadPoolExecutor(max_workers=len(pods)) as pool:
        futures = [
            pool.submit(backend.get_scheduling_decision, pod, nodes) for pod in pods
        ]
        for fut in futures:
            fut.result(timeout=max(deadline - time.monotonic(), 1.0))
    while backend.engine.wave_prewarm_backlog() > 0:
        if time.monotonic() > deadline:
            raise TimeoutError("sibling wave prewarm did not drain")
        time.sleep(0.05)


async def _serve(scheduler, cluster, pods, timeout_s: float) -> None:
    """The `cli run` loop over a burst: start the watch, add the pods, wait
    until the cluster has bound them all (or the deadline — unbound pods
    are reported by the caller), stop."""
    task = asyncio.create_task(scheduler.run())
    try:
        for pod in pods:
            cluster.add_pod(pod)

        async def all_bound() -> None:
            # 1 ms: stop before the scheduler's idle prefix-prewarm tick
            # (0.25 s) can queue a post-bind snapshot's prefill behind us
            while cluster.bind_count < len(pods):
                await asyncio.sleep(0.001)

        try:
            await asyncio.wait_for(all_bound(), timeout=timeout_s)
        except asyncio.TimeoutError:
            pass
    finally:
        scheduler.stop()
        cluster.close()
        await asyncio.wait_for(task, timeout=60)


def _reference_check(engine) -> dict:
    """The engine's cascade forward (shared-prefix part | causal chunk,
    the `auto` attention — Mosaic kernels on the chip) against the plain
    full-attention forward on the same 300 tokens, at the engine's own
    params: last-token logits must be finite, vocab-wide and agree."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_llm_scheduler_tpu.models.llama import (
        forward_prefill,
        forward_prefill_suffix_dense,
    )

    cfg, params = engine.cfg, engine.params
    n_prefix, n_suffix = 200, 100
    ids = np.random.default_rng(0).integers(
        3, engine.tokenizer.vocab_size, size=n_prefix + n_suffix
    )
    full = np.zeros((1, 384), np.int32)
    full[0, : ids.size] = ids
    logits, k_all, v_all = jax.jit(forward_prefill, static_argnums=(1,))(
        params, cfg, jnp.asarray(full), jnp.asarray([ids.size], jnp.int32)
    )
    ref = np.asarray(logits[0, ids.size - 1], np.float32)
    suffix = np.zeros((1, 128), np.int32)
    suffix[0, :n_suffix] = ids[n_prefix:]
    cascade = jax.jit(
        functools.partial(
            forward_prefill_suffix_dense, prefix_impl=engine.prefix_attn_impl
        ),
        static_argnums=(1,),
    )
    got, _, _ = cascade(
        params, cfg, jnp.asarray(suffix), jnp.asarray([n_suffix], jnp.int32),
        k_all[:, 0, :256], v_all[:, 0, :256], jnp.int32(n_prefix),
    )
    got = np.asarray(got[0], np.float32)
    diff = float(np.max(np.abs(got - ref)))
    scale = float(max(1.0, np.max(np.abs(ref))))
    return {
        "logits_shape": list(got.shape),
        "finite": bool(np.isfinite(got).all() and np.isfinite(ref).all()),
        "max_abs_diff": round(diff, 5),
        "ref_abs_max": round(scale, 4),
        "tolerance": round(LOGIT_TOL * scale, 5),
        "argmax_equal": bool(got.argmax() == ref.argmax()),
        "ok": bool(
            got.shape == (cfg.vocab_size,)
            and np.isfinite(got).all()
            and diff <= LOGIT_TOL * scale
        ),
    }


def _peak_bytes(devices) -> int:
    """Highest `peak_bytes_in_use` over the devices (0 where unreported)."""
    return max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices),
               default=0)


def run(cfg, *, nodes: int = NODES, pods: int = PODS, shapes: int = SHAPES) -> dict:
    """Build the stack as `cli run --fake-cluster` does, set it up, serve
    one burst through `Scheduler.run()`, check the outcome. Returns the
    summary; `summary["failures"]` is empty iff every check held."""
    t_start = time.perf_counter()
    deadline = time.monotonic() + DEADLINE_S
    import jax

    from k8s_llm_scheduler_tpu.cli import _build_stack
    from k8s_llm_scheduler_tpu.cluster.interface import raw_pod_to_spec
    from k8s_llm_scheduler_tpu.models.llama import param_count
    from k8s_llm_scheduler_tpu.observability.profiler import measure_dispatch_rtt_ms
    from k8s_llm_scheduler_tpu.testing import pod_burst, synthetic_cluster

    compiles = _Takes()
    cluster = synthetic_cluster(nodes)
    scheduler, backend = _build_stack(cfg, cluster)
    engine = backend.engine
    peak_after_build = _peak_bytes(jax.local_devices())
    try:
        burst = pod_burst(pods, distinct_shapes=shapes)
        _warm_up(
            backend, cluster.get_node_metrics(),
            [raw_pod_to_spec(p) for p in burst[: engine.max_slots]],
            deadline,
        )
        setup_s = time.perf_counter() - t_start
        first = compiles.mark["programs"]
        compiled = {"setup": compiles.take()}
        # by program (jit(wave), jit(prefix_prefill_kv), ...): where set-up went
        print(json.dumps({"setup_programs": compiles.log.table(first, compiles.mark["programs"])}),
              file=sys.stderr)
        engine_before = dict(engine.stats)

        t_serve = time.perf_counter()
        asyncio.run(
            _serve(scheduler, cluster, burst, max(deadline - time.monotonic(), 1.0))
        )
        serve_s = time.perf_counter() - t_serve
        compiled["serve"] = compiles.take()
        stats = scheduler.get_stats()
        reference = _reference_check(engine)
        compiled["reference"] = compiles.take()
        engine_stats = backend.get_stats()
        attention_shapes = sorted(
            f"{site} q{list(q)} kv{kv}: {how}"
            for (site, q, kv), how in engine.prefix_attn_impl.resolved.items()
        )
        bytes_in_use = [
            (d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in jax.local_devices()
        ]
        rtt_ms = measure_dispatch_rtt_ms(samples=20)
    finally:
        backend.close()

    served = {
        k: engine_stats[k] - engine_before.get(k, 0)
        for k in ("waves", "wave_model_calls", "fused_chunks", "fused_fallbacks",
                  "chunks", "packed_admissions",
                  "prefix_prefills", "requests", "completed")
    }
    drivers = [
        name for name, key in (
            ("wave_block_decode", "waves"), ("fused_while_loop", "fused_chunks"),
            ("sparse_chunked", "chunks"),
        ) if served[key]
    ]
    client, breaker = stats["client"], stats["client"].get("circuit_breaker", {})
    attention = engine_stats.get("attention_impls", {})
    devices = jax.local_devices()
    on_tpu = devices[0].platform == "tpu"

    failures = []

    def check(ok: bool, what: str) -> None:
        if not ok:
            failures.append(what)

    check(cluster.bind_count == pods, f"bound {cluster.bind_count} of {pods} pods")
    check(stats["llm_decisions"] >= shapes,
          f"llm_decisions {stats['llm_decisions']} < {shapes}")
    for key in ("fallback_decisions", "failed_bindings", "unschedulable"):
        check(stats[key] == 0, f"{key} = {stats[key]}")
    for key in ("failed_requests", "invalid_decisions", "deadline_timeouts"):
        check(client.get(key, 0) == 0, f"client.{key} = {client.get(key)}")
    # every model decision took ONE engine request and it completed: a
    # timeout or engine error that a client retry papered over still counts
    check(served["requests"] == served["completed"] == stats["llm_decisions"]
          == client["successful_requests"],
          f"engine requests {served['requests']} / completed "
          f"{served['completed']} != llm decisions {stats['llm_decisions']}: "
          f"a backend attempt failed or timed out and was retried")
    check(breaker.get("trips", 0) == 0 and breaker.get("state") == "closed",
          f"circuit breaker {breaker}")
    check(engine_stats["wave_prewarm_failures"] == 0,
          f"wave_prewarm_failures = {engine_stats['wave_prewarm_failures']}")
    check(bool(drivers), f"no decode driver served the burst: {served}")
    check(reference["ok"], f"cascade forward disagrees with reference: {reference}")
    if on_tpu:
        # per call site, every traced geometry on a Mosaic-compiled kernel
        wrong = {
            site: by_impl for site, by_impl in attention.items()
            if set(by_impl) - {"pallas", "pallas_shard_map"}
        }
        check(bool(attention) and not wrong,
              f"auto attention did not resolve to compiled Pallas kernels: "
              f"{wrong or 'no call site traced'} ({attention_shapes})")
    check(compiled["serve"]["programs_over_1s"] == 0,
          f"programs compiled inside the serve phase: {compiled['serve']}")

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    return {
        "failures": failures,
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                   "count": len(jax.devices())},
        "versions": {"jax": jax.__version__,
                     "jaxlib": importlib.metadata.version("jaxlib"),
                     "libtpu": libtpu},
        "model": {"name": engine.cfg.name, "params": param_count(engine.params),
                  "n_layers": engine.cfg.n_layers, "d_model": engine.cfg.d_model,
                  "vocab_size": engine.cfg.vocab_size,
                  "dtype": jax.numpy.dtype(engine.cfg.dtype).name,
                  "weights": "random-init seed 0",
                  "mesh": dict(engine.mesh.shape) if engine.mesh is not None else None},
        "workload": {"nodes": nodes, "pods": pods, "distinct_shapes": shapes,
                     "max_batch": engine.max_slots},
        "bound": cluster.bind_count,
        "decisions_by_source": {"llm": stats["llm_decisions"],
                                "cache": stats["cache_decisions"],
                                "fallback": stats["fallback_decisions"]},
        "breaker": breaker,
        "decode_driver": drivers,
        "served": served,
        "attention_impls": attention,
        "attention_shapes": attention_shapes,
        "reference": reference,
        "setup_seconds": round(setup_s, 2),
        "serve_seconds": round(serve_s, 3),
        "compiled": compiled,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "peak_bytes_in_use": _peak_bytes(devices),
        "peak_bytes_after_build": peak_after_build,
        "bytes_in_use_per_device": bytes_in_use,
        "temperature": cfg.get("llm.temperature"),
        "dispatch_to_device_get_ms": rtt_ms,
    }


def main() -> int:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU — JAX came up on {devices[0].platform!r} "
            f"({devices}); this smoke never passes on a CPU",
            file=sys.stderr,
        )
        return 2
    from k8s_llm_scheduler_tpu.logging_setup import setup_logging

    cfg = smoke_config()
    setup_logging(  # as cli.main does; stderr, so stdout stays the result
        level=cfg.get("logging.level"), fmt=cfg.get("logging.format"),
        file=cfg.get("logging.file"),
    )
    summary = run(cfg)
    if summary["failures"]:
        print(json.dumps(summary, default=str), file=sys.stderr)
        for line in summary["failures"]:
            print(f"chip_smoke: FAILED: {line}", file=sys.stderr)
        return 1
    print(json.dumps(summary, default=str))
    print(json.dumps({"ok": True, "device": summary["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
