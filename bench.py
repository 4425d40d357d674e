"""Benchmark suite: decision latency, burst throughput, long-context prefill,
and model-level MFU/throughput (BASELINE metrics).

Drives the COMPLETE stack — FakeCluster snapshot -> prompt -> in-tree JAX
Llama with grammar-constrained fused decode -> validation -> bind — on the
real TPU chip.

The reference publishes no numbers (BASELINE.md: "not published"); its
operating point is a remote HF chat_completion per pod with a 60s timeout
(reference config.yaml:10) and seconds-scale round trips. The BASELINE
north-star target is p50 < 200 ms on a burst, zero external API calls —
vs_baseline here is target_ms / measured_p50 (>1.0 beats the target).

Default run (`python bench.py`) executes the SUITE: every BASELINE preset
(default, burst1000, steady, longctx) on the bench-size model, the default
and burst1000 presets again on the BASELINE 1B model (with cold-leader /
warm-cache p50s split out), and model-throughput microbenches (prefill
tok/s, decode tok/s, MFU). One JSON line per result is printed as it
completes; the second-to-last line is the full suite object, and the LAST
line is a COMPACT headline — the 1B default-preset p50 — small enough that
tail-capture always parses it.

Usage:
    python bench.py                          # full suite
    python bench.py --preset burst1000       # one preset, one line
    python bench.py --preset throughput --model llama-3.1-8b-instruct \
        --quantize int8                      # model microbench only
"""

from __future__ import annotations

import argparse
import asyncio
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

TARGET_P50_MS = 200.0

# FLOP accounting + peak-TFLOPs table now live in observability/profiler.py
# (the continuous profiler's MFU loss decomposition must share one set of
# books with the bench headline); re-exported here so bench callers and
# tools keep their import path.
from k8s_llm_scheduler_tpu.observability.profiler import (  # noqa: E402
    PEAK_BF16_TFLOPS,
    attn_flops_per_token,
    detect_peak_tflops,
    matmul_flops_per_token,
    measure_dispatch_rtt_ms,
)
from k8s_llm_scheduler_tpu.testing import BPE_FIXTURE  # noqa: E402

_ = PEAK_BF16_TFLOPS  # re-export (unused-name guard)


def build_cfg(name: str):
    from k8s_llm_scheduler_tpu.models.configs import LlamaConfig, get_config

    if name == "bench":
        # Big enough that the MXU does real work, small enough to compile in
        # seconds — the architecture is identical to the 1B/8B/70B ladder.
        # vocab matches the committed BPE fixture (assets/bpe4k): the preset
        # benches run REAL BPE-length prompts (a 64-node cluster prompt is
        # ~3.7k BPE tokens vs ~10.5k byte tokens). max_seq_len covers the
        # longctx preset's 256-node prompt.
        return LlamaConfig(
            name="bench", vocab_size=1280, d_model=512, n_layers=6, n_heads=8,
            n_kv_heads=4, d_ff=1408, max_seq_len=65536, rope_theta=500000.0,
            tie_embeddings=True,
        )
    if name == "bench-tp":
        # The "bench" geometry with FULL kv heads: every point in the
        # tp-serving table (2/4/8) must divide n_heads, n_kv_heads, d_ff
        # and vocab (validate_specs_divisibility); "bench"'s kv4 caps the
        # ladder at tp=4. Same layer count / widths otherwise, so the
        # absolute numbers stay comparable to the rest of the suite.
        return LlamaConfig(
            name="bench-tp", vocab_size=1280, d_model=512, n_layers=6,
            n_heads=8, n_kv_heads=8, d_ff=1408, max_seq_len=65536,
            rope_theta=500000.0, tie_embeddings=True,
        )
    return get_config(name)


# --------------------------------------------------- FLOP accounting (cont.)
def param_count(cfg) -> int:
    d, hd = cfg.d_model, cfg.head_dim
    per_layer = (
        d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd
        + cfg.n_heads * hd * d + 3 * d * cfg.d_ff + 2 * d  # norms
    )
    embed = cfg.vocab_size * d
    head = 0 if cfg.tie_embeddings else cfg.vocab_size * d
    return int(cfg.n_layers * per_layer + embed + head + d)


# BASELINE.md burst configs (reference publishes no numbers; these mirror the
# north-star table). Presets override only flags the user left at default.
PRESETS = {
    # standard operating point: mid-size cluster, bursty pods
    "default": {},
    # "1000-pod burst, continuous batching, 64-node cluster state"
    "burst1000": {"pods": 1000, "nodes": 64, "shapes": 32},
    # "256-node cluster, ~8k-token (BPE) per-node-metrics prompt":
    # chunked-prefill stress. Fewer slots: admission batch attends
    # (slots x suffix_bucket) queries against the long prefix. 3 rounds —
    # a single round has no median protection against a weather spike or
    # stray compile (one suite run recorded 4.4s where the preset
    # standalone measures ~130ms).
    "longctx": {"pods": 16, "nodes": 256, "shapes": 4, "rounds": 3, "slots": 4},
    # sustained arrivals instead of burst-at-t0: per-decision latency with a
    # WARM prefix/grammar, the operating point between bursts. Runs in the
    # default suite at 1 round (bounded); standalone runs get 2.
    "steady": {"pods": 128, "nodes": 32, "shapes": 16, "rounds": 2,
               "arrival_rate": 100.0},
    # policy arena (sim/): score the served decider's PLACEMENTS against
    # the fallback + teacher arms on one seeded scenario; per-wave latency
    # attribution rides along. rounds here = scenario WAVES. temperature 0:
    # the arena's determinism contract covers the model arm.
    "arena": {"pods": 256, "nodes": 64, "shapes": 16, "rounds": 4,
              "temperature": 0.0},
    # hot weight swaps under sustained decode load (rollout/hotswap.py):
    # identical-params swaps fire while arrival-paced pods keep the engine
    # in waves; reports swap-pause p50/p99 (admission-held wall time) and
    # asserts zero failed/dropped requests across every swap.
    "rollout": {"pods": 192, "nodes": 32, "shapes": 16, "rounds": 1,
                "arrival_rate": 150.0},
    # tracing-layer cost A/B (observability/spans): identical scheduler
    # runs with the flight recorder ON vs OFF over a host-bound stub
    # backend, arrival-paced so per-pod latency is decoupled from drain
    # order; asserts the traced p50 is < 2% over the untraced one.
    "obs-overhead": {"pods": 300, "nodes": 32, "shapes": 32, "rounds": 3,
                     "arrival_rate": 100.0},
    # fleet-scale serving (fleet/): N sharded scheduler replicas over one
    # in-memory cluster, each replica backed by its OWN simulated TPU
    # decision service (decisions serialize per replica — the device),
    # tiered decision caches over one fleet-shared L2. Pods are all
    # distinct shapes (every decision is a leader): the measurement is
    # exactly what replica count multiplies — model compute — not host
    # drain speed. The sim device time (20 ms/decision, serialized per
    # replica) dominates per-pod host work the way a real engine does;
    # at 16 replicas the shared host loop becomes the bottleneck and the
    # curve flattens — reported, not hidden. Reports decisions/s and
    # bind p50/p99 at replica counts 1/4/16; acceptance bar: 4 replicas
    # >= 2.5x the decisions/s of 1.
    "fleet": {"pods": 600, "nodes": 500, "shapes": 0, "rounds": 1},
    # elastic fleet autoscaler (fleet/autoscale.py): a seeded DIURNAL
    # arrival curve (trough -> ~19x peak -> trough, wave-quantized)
    # replayed against static-N baselines through REAL elastic fleets
    # (health-gated joins, drain-before-release removals, real binds);
    # per-wave latency is modeled deterministically from queue position
    # over the serving replica count (20ms simulated device time), so
    # the published SLO-burn-vs-replica-seconds frontier is exact and
    # replayable. Bars: the elastic arm must DOMINATE at least one
    # static arm on both axes, and every arm binds every pod exactly
    # once across all scale events (zero dropped, zero double-bound).
    "autoscale": {"pods": 600, "nodes": 64, "shapes": 32, "rounds": 1},
    # burst AFTER a cluster-state change: every round perturbs node usage
    # (so the cluster prefix differs from the engine's resident group),
    # idles perturb_idle seconds, then bursts — the production shape
    # (binds mutate state between bursts; SCALING.md burst1000 floor).
    # A/B the scheduler's prefix prewarming with --prefix-prewarm 0:
    # with it off the burst's first wave pays the prefix prefill + DFA
    # switch; with it on (default) the idle loop installs the new group
    # before the burst lands.
    "restate": {"pods": 1000, "nodes": 64, "shapes": 32,
                "perturb_idle": 1.0, "rounds": 3},
    # deterministic chaos plane (chaos/): every fault regime through its
    # harness stack, zero invariant violations required; publishes
    # recovery time, degraded-decision fraction, quality-vs-teacher
    "chaos": {"pods": 48, "nodes": 10, "rounds": 1},
    # durable decision plane (sched/journal.py + sched/recovery.py): the
    # three crash regimes (cold kill -> rebuild from disk) must keep
    # binds exactly-once ACROSS restarts; publishes per-restart MTTR and
    # the journal's decision-p50 overhead A/B (<2% bar). pods/nodes size
    # the crash scenarios; rounds pace the overhead A/B pairs.
    "recovery": {"pods": 48, "nodes": 10, "rounds": 3, "shapes": 8},
    # closed policy-improvement loop (learn/): the full seeded
    # mine -> finetune -> publish -> gate -> hot-swap cycle on a micro
    # REAL engine; asserts the promoted checkpoint strictly improves the
    # mined-weakness score vs the incumbent without regressing the base
    # arena, and that the cycle's trace replays byte-identically.
    # pods/nodes here size the MINING scenarios.
    "learn": {"pods": 36, "nodes": 6, "shapes": 6, "rounds": 1},
    # delta-prefill admission plane (engine/admission/ + sched/delta.py):
    # burst1000-shaped rounds where every round DRIFTS node usage first
    # (the production shape — binds mutate state between bursts), A/B'd
    # delta-encoded vs whole-prompt prompts on the real engine, plus one
    # steady (arrival-paced) round for the burst-vs-steady ratio, plus a
    # token-count-exact sublinearity table across 256 -> 10k-node
    # snapshots. Goal: burst p50 within ~1.5x of steady p50, and delta
    # prefill tokens/decision flat in node count while whole-prompt grows
    # linearly.
    "burst": {"pods": 1000, "nodes": 64, "shapes": 32, "rounds": 2,
              "perturb_idle": 0.5},
    # fused on-device decode runtime (engine/fused/): fused-vs-chunked
    # decode A/B on one engine + the scheduler-path decision p50 with
    # the dispatch round trip beside it. The fused claim is fewer
    # dispatch-gating sync boundaries per request — syncs/request is
    # measured for both arms.
    "decode": {"pods": 64, "nodes": 32, "shapes": 8, "rounds": 3},
    # GSPMD tensor-parallel serving plane (engine/sharded/): decisions/s
    # + MFU table at tp = 1/2/4/8 over ONE geometry-compatible model
    # ("bench-tp" — kv-heads widened to 8 so every point divides). Each
    # point shards params via serving_param_specs and runs the REAL
    # serving path (pinned prefix, paged KV, packed admission, fused
    # decode, grammar sampling) under the mesh. rounds = measured
    # pipelined waves per point. On a host-device mesh (CPU forced to 8
    # devices) the absolute numbers measure XLA:CPU, not ICI — recorded
    # as such — and the table's real assertion is the cross-tp greedy
    # token digest, which must not drift when the layout changes.
    "tp-serving": {"slots": 8, "rounds": 2, "max_new_tokens": 48,
                   "temperature": 0.0},
    # routed fast tier (sched/router.py): distill big + fast arms from
    # the same spread-lookahead teacher (fast = half-width student),
    # then arena-gate the routed hybrid against BOTH arms alone — the
    # hybrid must be no worse than either arm on every gate axis, and
    # the routing must actually MIX (both arms see decisions).
    "router": {"rounds": 1},
}


async def run_burst(
    scheduler, cluster, pods, timeout_s: float, arrival_rate: float | None = None
) -> tuple[dict[str, float], float]:
    """Schedule pods and report per-pod latency (bind time - enqueue time).

    arrival_rate=None: all pods enqueue at t0 (burst). Otherwise pods
    arrive uniformly at `arrival_rate` pods/sec (sustained load)."""
    bind_times: dict[str, float] = {}
    enqueue_times: dict[str, float] = {}
    orig_bind = cluster.bind_pod_to_node

    def timed_bind(pod_name, namespace, node_name):
        ok = orig_bind(pod_name, namespace, node_name)
        if ok:
            bind_times[pod_name] = time.perf_counter()
        return ok

    cluster.bind_pod_to_node = timed_bind
    try:
        t0 = time.perf_counter()
        for i, pod in enumerate(pods):
            if arrival_rate:
                target = t0 + i / arrival_rate
                delay = target - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
            enqueue_times[pod.name] = time.perf_counter()
            cluster.add_pod(pod)
        async def _drain() -> None:
            while cluster.bind_count < len(pods):
                await asyncio.sleep(0.005)

        # wait_for, not asyncio.timeout: the latter is 3.11+ and the
        # package floor is >=3.10
        await asyncio.wait_for(_drain(), timeout=timeout_s)
        latencies = {
            name: (t - enqueue_times[name]) * 1000.0
            for name, t in bind_times.items()
        }
        # wall time of the whole round: under arrival pacing the max
        # per-pod latency no longer approximates it
        wall_s = max(bind_times.values()) - t0
        return latencies, wall_s
    finally:
        cluster.bind_pod_to_node = orig_bind


def build_backend(args, delta_prompts: bool = False):
    from k8s_llm_scheduler_tpu.engine.local import build_local_backend

    cfg = build_cfg(args.model)
    # Size the paged KV pool from the model: a fixed page count that is fine
    # for the bench-size model is 17 GB at 8B scale. Budget ~1 GB.
    page_size = 128
    page_bytes = cfg.n_layers * page_size * cfg.n_kv_heads * cfg.head_dim * 2 * 2
    num_pages = max(64, min(1024, int(1e9 // page_bytes)))
    return build_local_backend(
        cfg=cfg,
        # the committed BPE fixture for EVERY preset model: benches measure
        # real-tokenizer prompt lengths, not byte-inflated ones (the engine
        # accepts a tokenizer smaller than the model's padded vocab, so
        # checkpoint-shaped 1B/8B configs run with the fixture too)
        tokenizer_path=BPE_FIXTURE,
        max_slots=args.slots,
        num_pages=num_pages,
        page_size=page_size,
        # small buckets serve the per-pod suffixes (shared-prefix path);
        # large ones serve the once-per-snapshot cluster-state prefix.
        prefill_buckets=(128, 256, 512, 1024, 2048, 4096, 8192, 16384),
        chunk_steps=args.chunk_steps,
        temperature=args.temperature,
        max_new_tokens=args.max_new_tokens,
        quantize=getattr(args, "quantize", None),
        delta_prompts=delta_prompts,
    )


async def bench_preset(args, backend=None) -> dict:
    from k8s_llm_scheduler_tpu.core.breaker import CircuitBreaker
    from k8s_llm_scheduler_tpu.core.cache import DecisionCache
    from k8s_llm_scheduler_tpu.sched.client import DecisionClient
    from k8s_llm_scheduler_tpu.sched.loop import Scheduler
    from k8s_llm_scheduler_tpu.testing import (
        SCHEDULER_NAME,
        pod_burst,
        synthetic_cluster,
    )

    own_backend = backend is None
    if own_backend:
        backend = build_backend(args)

    async def one_round(n_pods: int, round_id: str, timeout_s: float):
        cluster = synthetic_cluster(args.nodes)
        client = DecisionClient(
            backend,
            cache=DecisionCache(),
            breaker=CircuitBreaker(),
            retry_delay=0.1,
        )
        scheduler = Scheduler(
            cluster, cluster, client,
            scheduler_name=SCHEDULER_NAME, snapshot_ttl_s=300.0,
            max_concurrency=256,
            prefix_prewarm_s=float(getattr(args, "prefix_prewarm", 0.25)),
        )
        # Tag every bound pod with its decision source so per-pod latencies
        # split into cold (LLM leader — paid a real wave round trip) and
        # warm (cache hit or single-flight follower). All bind paths
        # converge on _note_bind, so the wrap sees every pod exactly once.
        sources: dict[str, str] = {}
        orig_note = scheduler._note_bind

        def tagging_note(ok, pod, decision):
            if ok:
                sources[pod.name] = decision.source.value
            orig_note(ok, pod, decision)

        scheduler._note_bind = tagging_note
        task = asyncio.create_task(scheduler.run())
        if getattr(args, "perturb_idle", 0):
            # Burst-after-state-change (restate preset): shift every
            # node's usage deterministically per round so the rendered
            # cluster prefix DIFFERS from the engine's resident group,
            # then idle so prefix prewarming (if enabled) can install the
            # new group before the burst lands. crc32, not hash():
            # per-process hash salting would randomize the perturbation
            # across the A and B runs of an A/B.
            import zlib

            seed = zlib.crc32(round_id.encode()) % 90
            for i, node in enumerate(cluster._nodes.values()):
                node.cpu_usage_percent = 5.0 + (i * 37 + seed) % 90
                node.memory_usage_percent = 5.0 + (i * 53 + seed) % 90
            await asyncio.sleep(float(args.perturb_idle))
        pods = pod_burst(n_pods, distinct_shapes=args.shapes)
        # distinct names per round so bind bookkeeping stays unambiguous
        import dataclasses as _dc

        pods = [_dc.replace(p, name=f"{round_id}-{p.name}") for p in pods]
        try:
            latencies, wall_s = await run_burst(
                scheduler, cluster, pods, timeout_s,
                arrival_rate=getattr(args, "arrival_rate", None),
            )
        finally:
            scheduler.stop()
            cluster.close()
            await asyncio.wait_for(task, timeout=30)
        return latencies, wall_s, scheduler.get_stats(), sources

    # Warmup at FULL burst size: compiles every program geometry the measured
    # rounds hit (prefix bucket for this node count, this grammar's wave
    # n_iters bucket) AND absorbs the first-full-round host-side overhead
    # (round-1 p50 ran ~40 ms hotter when warmup used fewer pods).
    await one_round(args.pods, round_id=f"{args.preset}-w", timeout_s=600.0)
    # Wait out the engine's sibling-geometry prewarm (the idle worker
    # compiles the OTHER wave row bucket at every bucket the warmup hit):
    # a straggler-timing ragged wave in a measured round must never pay a
    # cold jit (r03 longctx recorded a 5.1s mid-round stall from exactly
    # that). Engine-owner discipline: we only poll the read-only backlog.
    async def _drain_prewarm() -> None:
        while backend.engine.wave_prewarm_backlog() > 0:
            await asyncio.sleep(0.05)

    await asyncio.wait_for(_drain_prewarm(), timeout=600)

    profile_cm = None
    if getattr(args, "profile_dir", None):
        from k8s_llm_scheduler_tpu.observability.trace import device_trace

        profile_cm = device_trace(args.profile_dir)
        profile_cm.__enter__()

    # Median of N measured rounds: a single burst round has no protection
    # against one slow round (a stray compile, a busy host).
    rounds = []
    for r in range(args.rounds):
        latencies, wall_s, stats, sources = await one_round(
            args.pods, round_id=f"{args.preset}-{r + 1}", timeout_s=600.0
        )
        values = sorted(latencies.values())
        p50 = statistics.median(values)
        p99 = values[min(len(values) - 1, int(len(values) * 0.99))]
        # Cold = LLM-sourced decisions (the leaders, each paying a real
        # model wave); warm = cache hits + coalesced followers. Every round
        # starts with a FRESH decision cache, so cold-p50 is the honest
        # uncached per-shape latency at this model size.
        cold = sorted(
            lat for name, lat in latencies.items()
            if sources.get(name) == "llm"
        )
        warm = sorted(
            lat for name, lat in latencies.items()
            if sources.get(name) == "cache"
        )
        split = {
            "p50_cold_ms": round(statistics.median(cold), 2) if cold else None,
            "p50_warm_ms": round(statistics.median(warm), 2) if warm else None,
            "n_cold": len(cold),
            "n_warm": len(warm),
        }
        rounds.append((p50, p99, args.pods / wall_s, stats, split))
    if profile_cm is not None:
        profile_cm.__exit__(None, None, None)
    if own_backend:
        backend.close()

    rounds.sort(key=lambda t: t[0])
    # Lower-median: for odd round counts this is the true median; for even
    # counts it reports the lower middle rather than systematically picking
    # the worse round.
    p50, p99, pods_per_sec, stats, split = rounds[(len(rounds) - 1) // 2]
    decide = stats["phases"]["decide"]
    return {
        "metric": "p50_decision_latency_ms",
        "value": round(p50, 2),
        "unit": "ms",
        "vs_baseline": round(TARGET_P50_MS / p50, 3),
        "extra": {
            "p99_ms": round(p99, 2),
            **split,
            "pods": args.pods,
            "nodes": args.nodes,
            "shapes": args.shapes,
            "pods_per_sec": round(pods_per_sec, 2),
            # per-decision wall time inside the loop (excludes burst queue
            # wait) — semantically the reference's own latency metric
            # (reference scheduler.py:420 running avg of LLM call wall time)
            "decide_avg_ms": round(decide["avg_ms"], 2),
            # histogram-derived percentiles (observability/trace buckets):
            # the avg hid the decide tail every earlier round argued from
            "decide_p50_ms": round(decide.get("p50_ms", 0.0), 2),
            "decide_p95_ms": round(decide.get("p95_ms", 0.0), 2),
            "decide_p99_ms": round(decide.get("p99_ms", 0.0), 2),
            "round_p50s_ms": [round(r[0], 2) for r in rounds],
            "llm_decisions": stats["llm_decisions"],
            "cache_decisions": stats["cache_decisions"],
            "fallback_decisions": stats["fallback_decisions"],
            "model": args.model,
            # honesty marker (VERDICT r4 weak #6): every preset runs the
            # ARCHITECTURE at random init — "model" names the config, not
            # pretrained weights. Throughput/MFU are weight-independent.
            "weights": "random-init",
            "preset": args.preset,
            "prefix_prewarm_s": float(getattr(args, "prefix_prewarm", 0.25)),
            "baseline_note": "reference publishes no numbers; target p50<200ms (BASELINE.md)",
        },
    }


# --------------------------------------------------------- delta admission
def _snapshot_token_table(node_counts, drift_nodes: int = 8,
                          decisions_per_burst: int = 32) -> list[dict]:
    """Prefill tokens per decision, delta-encoded vs whole-prompt, across
    synthetic snapshot sizes — TOKEN-COUNT-EXACT (tokenizer-level, no
    model): the figure is a property of the encoding, and counting it
    directly is both honest and fast enough to include 10k nodes.

    `drift_nodes` is FIXED across cluster sizes on purpose: between two
    bursts, the nodes that changed are the ones binds touched — a
    property of the burst, not of the cluster. That is exactly why the
    delta path is sublinear: its prefill cost follows the drift while the
    whole-prompt render follows the cluster."""
    import dataclasses as _dc

    from k8s_llm_scheduler_tpu.engine.tokenizer import HFTokenizerAdapter
    from k8s_llm_scheduler_tpu.sched.delta import SnapshotDeltaEncoder
    from k8s_llm_scheduler_tpu.testing import synthetic_cluster

    tok = HFTokenizerAdapter(BPE_FIXTURE)
    rows = []
    for n in node_counts:
        nodes = list(synthetic_cluster(n).get_node_metrics())
        drifted = list(nodes)
        for i in range(min(drift_nodes, n)):
            j = (i * 29) % n  # deterministic spread over the cluster
            drifted[j] = _dc.replace(
                drifted[j],
                cpu_usage_percent=(drifted[j].cpu_usage_percent + 13.0) % 95.0,
                memory_usage_percent=(drifted[j].memory_usage_percent + 7.0) % 95.0,
            )
        enc = SnapshotDeltaEncoder(repin_fraction=1.1)  # never re-pin here
        pin = enc.encode(nodes)          # burst 1 pins the snapshot
        dp = enc.encode(drifted)         # burst 2 rides the delta
        assert not dp.repinned and dp.delta_nodes > 0
        whole_tokens = len(tok.encode(dp.pin_text))
        delta_tokens = len(tok.encode(dp.cluster_part)) - whole_tokens
        rows.append({
            "nodes": n,
            "whole_prefix_tokens": whole_tokens,
            "delta_prefix_tokens": delta_tokens,
            "whole_tokens_per_decision": round(
                whole_tokens / decisions_per_burst, 1
            ),
            "delta_tokens_per_decision": round(
                delta_tokens / decisions_per_burst, 1
            ),
        })
        del pin
    return rows


async def burst_bench(args) -> dict:
    """`--preset burst`: the delta-prefill admission plane under a
    burst1000-shaped arrival.

    Three measurements in one report:
    - REAL-ENGINE burst rounds with drift before every round
      (perturb_idle — binds mutate state between bursts) through the
      delta-encoded prompt path, and the same rounds whole-prompt, with
      measured prefill tokens/decision from the engine's own books
      (prefix prefills count only non-reused tokens);
    - one STEADY (arrival-paced) round on the delta backend — the
      burst-vs-steady p50 ratio is the headline (bar: within ~1.5x);
    - the token-count-exact sublinearity table across 256 -> 10k-node
      snapshots (fixed drift — see _snapshot_token_table)."""
    table = _snapshot_token_table((256, 1024, 4096, 10000))

    def _tokens_per_decision(backend) -> float | None:
        stats = backend.get_stats()
        return stats.get("prefill_tokens_per_decision")

    # delta arm: drifted bursts + one steady round
    backend = build_backend(args, delta_prompts=True)
    try:
        burst_delta = await bench_preset(args, backend=backend)
        delta_tpd = _tokens_per_decision(backend)
        delta_stats = {
            k: v for k, v in backend.get_stats().items()
            if k in ("delta", "pins", "prefix_reused_tokens",
                     "packed_admissions")
        }
        steady_args = argparse.Namespace(**vars(args))
        steady_args.arrival_rate = 100.0
        steady_args.perturb_idle = 0.0
        steady_args.pods = min(args.pods, 256)
        steady_args.rounds = 1
        steady = await bench_preset(steady_args, backend=backend)
    finally:
        backend.close()

    # whole-prompt arm: identical drifted bursts, no delta encoding
    backend = build_backend(args, delta_prompts=False)
    try:
        burst_whole = await bench_preset(args, backend=backend)
        whole_tpd = _tokens_per_decision(backend)
    finally:
        backend.close()

    burst_p50 = burst_delta["value"]
    steady_p50 = steady["value"]
    ratio = round(burst_p50 / steady_p50, 3) if steady_p50 else None
    return {
        "metric": "burst_p50_over_steady_p50",
        "value": ratio,
        "unit": "ratio",
        "extra": {
            "model": args.model,
            "weights": "random-init",
            "pods": args.pods,
            "nodes": args.nodes,
            "shapes": args.shapes,
            "bar": "burst p50 within ~1.5x of steady p50",
            "bar_met": bool(ratio is not None and ratio <= 1.5),
            "burst_p50_ms": burst_p50,
            "steady_p50_ms": steady_p50,
            "burst_delta": burst_delta["extra"],
            "burst_whole_prompt": {
                "p50_ms": burst_whole["value"],
                **{k: burst_whole["extra"][k] for k in
                   ("p99_ms", "p50_cold_ms", "pods_per_sec")},
            },
            # measured on the engine's own books (non-reused tokens only)
            "prefill_tokens_per_decision": {
                "delta": delta_tpd,
                "whole_prompt": whole_tpd,
            },
            "delta_stats": delta_stats,
            # token-count-exact sublinearity across snapshot sizes
            "snapshot_scaling": table,
            "baseline_note": (
                "delta prefill tokens/decision must stay ~flat in node "
                "count while whole-prompt grows linearly (ROADMAP item 2)"
            ),
        },
    }


# ------------------------------------------------------------- rollout swap
async def rollout_bench(args) -> dict:
    """`--preset rollout`: hot-swap pause under active decode load.

    Runs the full stack at a sustained arrival rate while performing
    identical-params hot swaps through LocalLLMBackend.run_quiesced — the
    quiesce path a real promotion takes (hold admissions, drain waves,
    swap the params pointer, invalidate the prefix cache), with identical
    weights so decision QUALITY is unchanged and only the machinery is
    measured. Reports swap-pause p50/p99 and asserts every pod bound with
    zero failures across every swap."""
    from k8s_llm_scheduler_tpu.core.breaker import CircuitBreaker
    from k8s_llm_scheduler_tpu.core.cache import DecisionCache
    from k8s_llm_scheduler_tpu.sched.client import DecisionClient
    from k8s_llm_scheduler_tpu.sched.loop import Scheduler
    from k8s_llm_scheduler_tpu.testing import (
        SCHEDULER_NAME,
        pod_burst,
        synthetic_cluster,
    )

    backend = build_backend(args)
    engine = backend.engine
    cache = DecisionCache(max_size=4096)
    n_swaps = int(getattr(args, "swaps", None) or 6)
    pauses_ms: list[float] = []
    try:
        cluster = synthetic_cluster(args.nodes)
        client = DecisionClient(
            backend, cache=cache, breaker=CircuitBreaker(), retry_delay=0.1,
        )
        scheduler = Scheduler(
            cluster, cluster, client,
            scheduler_name=SCHEDULER_NAME, snapshot_ttl_s=300.0,
            max_concurrency=256,
        )
        task = asyncio.create_task(scheduler.run())
        pods = pod_burst(args.pods, distinct_shapes=args.shapes)

        swap_done = asyncio.Event()

        async def swap_loop():
            # identical-params swap: the exact quiesce/invalidate path of a
            # promotion, with a no-op weight change. Spaced across the run
            # so swaps land while waves are genuinely in flight.
            interval = max(args.pods / args.arrival_rate / (n_swaps + 1), 0.05)
            for _ in range(n_swaps):
                await asyncio.sleep(interval)

                def do_swap():
                    engine.swap_params(engine.params)
                    cache.bump_generation()

                _, pause_s = await asyncio.to_thread(
                    backend.run_quiesced, do_swap
                )
                pauses_ms.append(pause_s * 1000.0)
            swap_done.set()

        swapper = asyncio.ensure_future(swap_loop())
        try:
            latencies, wall_s = await run_burst(
                scheduler, cluster, pods, timeout_s=600.0,
                arrival_rate=args.arrival_rate,
            )
            await asyncio.wait_for(swap_done.wait(), timeout=120.0)
        finally:
            swapper.cancel()
            scheduler.stop()
            cluster.close()
            await asyncio.wait_for(task, timeout=30)
        stats = scheduler.get_stats()
    finally:
        backend.close()

    assert len(latencies) == args.pods, (
        f"dropped requests across swaps: {len(latencies)}/{args.pods} bound"
    )
    assert stats["failed_bindings"] == 0, stats
    assert stats["client"]["failed_requests"] == 0, stats["client"]
    pauses = sorted(pauses_ms)
    lat = sorted(latencies.values())
    return {
        "metric": "rollout_swap_pause_ms",
        "value": round(statistics.median(pauses), 2),
        "unit": "ms",
        "extra": {
            "p99_ms": round(pauses[min(len(pauses) - 1, int(len(pauses) * 0.99))], 2),
            "pauses_ms": [round(p, 2) for p in pauses],
            "swaps": len(pauses),
            "weight_swaps": stats["client"]["engine"].get("weight_swaps", 0),
            "pods": args.pods,
            "nodes": args.nodes,
            "arrival_rate": args.arrival_rate,
            "pod_p50_ms": round(statistics.median(lat), 2),
            "pod_p99_ms": round(lat[min(len(lat) - 1, int(len(lat) * 0.99))], 2),
            "failed_bindings": stats["failed_bindings"],
            "fallback_decisions": stats["fallback_decisions"],
            "cache_generation": cache.stats()["generation"],
            "model": args.model,
            "weights": "random-init",
            "note": "identical-params swaps: quiesce machinery only",
        },
    }


# ------------------------------------------------------------- obs overhead
async def obs_overhead_bench(args) -> dict:
    """`--preset obs-overhead`: what does the tracing layer cost?

    The SAME scheduler stack (full path: snapshot -> decide -> bind, no
    decision cache so every pod pays a real backend call) runs arrival-
    paced rounds alternating the observability layer OFF and ON. ON now
    means the FULL plane: flight-recorder tracing plus a live SLO
    burn-rate engine (observability/slo.py — a latency + an error-rate
    objective evaluating at 20 Hz, 200x the production 10 s cadence, so
    the measurement over-states the steady-state cost on purpose). The
    stub backend carries a fixed 10 ms decision cost — 20-50x BELOW a
    real model wave, so the measured overhead percentage is an upper
    bound on what production serving would see. Per-arm p50 is the min of
    round medians (host-noise filter applied identically to both arms);
    asserts the observability layer costs < 2% of decision p50. The wave
    profiler's per-record cost is measured directly (it hooks waves, not
    decisions — the stub path has none) and reported beside the span
    micro-cost."""
    import dataclasses as _dc

    from k8s_llm_scheduler_tpu.engine.backend import StubBackend
    from k8s_llm_scheduler_tpu.observability import spans
    from k8s_llm_scheduler_tpu.observability.slo import (
        SloEngine,
        SloObjective,
    )
    from k8s_llm_scheduler_tpu.sched.client import DecisionClient
    from k8s_llm_scheduler_tpu.sched.loop import Scheduler
    from k8s_llm_scheduler_tpu.testing import (
        SCHEDULER_NAME,
        pod_burst,
        synthetic_cluster,
    )

    # 10 ms/decision: ~20-50x below a real model wave, but large enough
    # that the 2% budget (~300 us) sits well clear of host scheduling
    # noise (~100 us after the min-of-rounds filter) while the measured
    # tracing cost itself is ~50 us/decision
    stub_latency_s = 0.010

    async def one_round(tag: str, enabled: bool) -> float:
        spans.configure(enabled=enabled)
        cluster = synthetic_cluster(args.nodes)
        client = DecisionClient(
            StubBackend(latency_s=stub_latency_s), cache=None,
        )
        scheduler = Scheduler(
            cluster, cluster, client,
            scheduler_name=SCHEDULER_NAME, snapshot_ttl_s=300.0,
            max_concurrency=256, prefix_prewarm_s=0.0,
        )
        slo = None
        if enabled:
            slo = SloEngine(
                [
                    SloObjective(
                        name="decide_latency", kind="latency",
                        phase="decide", threshold_ms=5000.0, budget=0.01,
                    ),
                    SloObjective(
                        name="bind_errors", kind="error_rate",
                        numerator="failed_bindings",
                        denominator="total_scheduled", budget=0.05,
                    ),
                ],
                scheduler.get_stats,
            )
            slo.start(interval_s=0.05)  # 200x the production cadence
        task = asyncio.create_task(scheduler.run())
        pods = [
            _dc.replace(p, name=f"{tag}-{p.name}")
            for p in pod_burst(args.pods, distinct_shapes=args.shapes)
        ]
        try:
            latencies, _ = await run_burst(
                scheduler, cluster, pods, timeout_s=300.0,
                arrival_rate=args.arrival_rate,
            )
        finally:
            scheduler.stop()
            cluster.close()
            await asyncio.wait_for(task, timeout=30)
            if slo is not None:
                slo.stop()
        return statistics.median(latencies.values())

    was_enabled = spans.enabled()
    try:
        await one_round("warm", enabled=True)  # warm pools/paths, discarded
        p50s: dict[bool, list[float]] = {False: [], True: []}
        for r in range(args.rounds):
            # OFF first then ON within each round: weather drift between
            # rounds cancels inside the pair
            p50s[False].append(await one_round(f"off{r}", enabled=False))
            p50s[True].append(await one_round(f"on{r}", enabled=True))

        # per-span micro cost, measured directly (goes to SCALING.md)
        spans.configure(enabled=True)
        n_micro = 5000
        with spans.start_trace("micro", recorder=spans.FlightRecorder(1)):
            t0 = time.perf_counter()
            for _ in range(n_micro):
                with spans.span("x"):
                    pass
            span_us = (time.perf_counter() - t0) / n_micro * 1e6

        # per-WAVE profiler record cost, measured directly: the profiler
        # hooks the engine's wave path (one record per ~8-16 decisions),
        # so its budget share is profiler_wave_us / (decisions-per-wave *
        # decision p50) — report the raw figure
        from k8s_llm_scheduler_tpu.observability.profiler import (
            EngineProfiler,
        )

        prof = EngineProfiler(cfg=None, window=256)
        n_waves_micro = 2000

        class _H:  # stand-in handle: the profiler keys on identity only
            pass

        t0 = time.perf_counter()
        for _ in range(n_waves_micro):
            h = _H()
            tp = time.perf_counter()
            prof.on_submit(
                h, tp, tp, suffix_tokens=250, n_requests=8,
                prefix_len=1000, cold_compile=False,
            )
            prof.note_admission(h, tp)
            prof.note_ready(h)
            prof.on_harvest(
                h, tp, tp, tp, decode_tokens=70, model_calls=9,
                ready_at_entry=True,
            )
        profiler_wave_us = (
            (time.perf_counter() - t0) / n_waves_micro * 1e6
        )
    finally:
        spans.configure(enabled=was_enabled)

    p50_off = min(p50s[False])
    p50_on = min(p50s[True])
    overhead_pct = (p50_on - p50_off) / p50_off * 100.0
    assert overhead_pct < 2.0, (
        f"observability overhead {overhead_pct:.2f}% >= 2% of decision "
        f"p50 (on {p50_on:.3f}ms vs off {p50_off:.3f}ms)"
    )
    return {
        "metric": "obs_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": "pct_of_p50",
        "extra": {
            "p50_traced_ms": round(p50_on, 3),
            "p50_untraced_ms": round(p50_off, 3),
            "round_p50s_off_ms": [round(v, 3) for v in p50s[False]],
            "round_p50s_on_ms": [round(v, 3) for v in p50s[True]],
            "span_overhead_us": round(span_us, 2),
            "profiler_wave_us": round(profiler_wave_us, 2),
            "pods": args.pods,
            "nodes": args.nodes,
            "arrival_rate": args.arrival_rate,
            "stub_latency_ms": stub_latency_s * 1000.0,
            "threshold_pct": 2.0,
            "on_arm": "tracing + slo engine @20Hz (200x prod cadence)",
            "note": (
                "stub backend at 10ms/decision — ~20-50x below a real "
                "wave, so this percentage upper-bounds production "
                "overhead; profiler cost is per WAVE (~8-16 decisions), "
                "measured as its own micro figure"
            ),
        },
    }


# ---------------------------------------------------------------- sim arena
def arena_bench(args) -> dict:
    """`--preset arena`: the policy arena (sim/) with the REAL local
    engine as the LLM arm — the first bench that scores the served
    decider's PLACEMENTS against the `resource_balanced` fallback and the
    sim/teacher.py spread-lookahead reference on one seeded scenario
    (round-5 VERDICT: that comparison had never been measured). Greedy
    (temperature 0): the arena's determinism contract — identical
    placements and scores for a given --seed — holds for the model arm
    too. Emits one BENCH-style JSON object with per-arm scores and
    per-wave latency attribution (prefill vs admission vs decode vs
    bind)."""
    from k8s_llm_scheduler_tpu.sim import (
        ArmSpec,
        HeuristicBackend,
        ScenarioSpec,
        generate_scenario,
        run_arena,
        save_trace,
        teacher_arm,
    )

    backend = build_backend(args)
    spec = ScenarioSpec(
        name="bench-arena",
        seed=args.seed if args.seed is not None else 0,
        n_nodes=args.nodes,
        n_pods=args.pods,
        shapes=args.shapes,
        arrival="waves",
        n_waves=max(1, args.rounds),
        constraint_mix=("uniform", "selector", "tainted"),
        taint_frac=0.2,
    )
    scenario = generate_scenario(spec)
    arms = [
        ArmSpec(name="llm", kind="stack", make=lambda: backend, owned=False),
        ArmSpec(
            name="resource_balanced", kind="stack",
            make=lambda: HeuristicBackend("resource_balanced"),
        ),
        teacher_arm(),
    ]
    try:
        report = run_arena(scenario, arms, wave_timeout_s=600.0)
    finally:
        # prefill tokens per finished decision (admission-plane headline;
        # prefix prefills count only non-reused tokens) — read before the
        # backend is torn down, off the engine's own books
        prefill_tpd = backend.get_stats().get("prefill_tokens_per_decision")
        backend.close()
    if getattr(args, "trace", None):
        save_trace(report, args.trace)
    report.pop("_traces")
    llm = report["arms"]["llm"]
    return {
        "metric": "sim_arena",
        "value": llm["scores"]["spread"],
        "unit": "pod_fill_spread",
        "extra": {
            "model": args.model,
            "weights": "random-init",
            "prefill_tokens_per_decision": prefill_tpd,
            "seed": spec.seed,
            "pods": spec.n_pods,
            "nodes": spec.n_nodes,
            "shapes": spec.shapes,
            "waves": len(scenario.waves),
            "arms": {
                name: {
                    "scores": arm["scores"],
                    "placements_digest": arm["placements_digest"],
                    "waves": arm["waves"],
                }
                for name, arm in report["arms"].items()
            },
        },
    }


def chaos_bench(args) -> dict:
    """`--preset chaos`: every chaos regime (chaos/faults.REGIMES) runs
    seeded through its harness stack, and the preset FAILS unless every
    run finishes with zero invariant violations. Published per regime:
    recovery time (waves + ms after the last fault wave until a clean
    wave), degraded-decision fraction (the ladder's engagement meter —
    asserted >0 for the brownout regime, or the run was fault-free and
    proved nothing), and placement quality vs the fault-free teacher
    policy. `value` is the worst recovery time in waves across regimes."""
    from k8s_llm_scheduler_tpu.chaos import REGIMES, run_chaos

    seed = args.seed if args.seed is not None else 0
    regimes = {}
    violations = 0
    worst_recovery = 0
    for regime in sorted(REGIMES):
        # geometry comes from PRESETS["chaos"] via the merged args —
        # the mechanism every other preset tunes through
        report = run_chaos(
            regime, seed=seed, n_waves=6,
            n_nodes=args.nodes, n_pods=args.pods,
        )
        inv = report["invariants"]
        violations += len(inv["violations"])
        recovery = report["recovery"]["recovery_waves"]
        if recovery is None:
            recovery = 99  # never recovered inside the run: loud
        worst_recovery = max(worst_recovery, recovery)
        regimes[regime] = {
            "mode": report["mode"],
            "clean": inv["clean"],
            "checks": inv["checks"],
            "plan_digest": report["plan_digest"],
            "injections": report["injections"],
            "recovery_waves": report["recovery"]["recovery_waves"],
            "recovery_ms": report["recovery"]["recovery_ms"],
            "degraded_fraction": report["degraded_fraction"],
            "bound_frac": report["scores"]["bound_frac"],
            "quality": report.get("quality"),
            "wall_ms": report["wall_ms"],
        }
        if "autoscale" in report:
            regimes[regime]["autoscale"] = {
                k: report["autoscale"][k]
                for k in ("scale_ups", "scale_downs", "join_failures")
            }
            regimes[regime]["scale_events"] = [
                (e["tick"], e["action"]) for e in report["scale_events"]
            ]
    assert violations == 0, (
        f"{violations} invariant violation(s) across chaos regimes: "
        + json.dumps({r: v for r, v in regimes.items() if not v["clean"]})
    )
    # the ladder must have actually engaged somewhere, or the brownout
    # regime was fault-free and the preset proved nothing
    assert regimes["brownout"]["degraded_fraction"] > 0, (
        "brownout regime shed no decisions — the degradation ladder "
        "never engaged"
    )
    # scale-thrash: flapping arrival at the threshold every wave must
    # produce BOUNDED oscillation — membership changes strictly fewer
    # than waves (never one per wave; hysteresis + cooldowns working)
    thrash = regimes["scale-thrash"]["autoscale"]
    thrash_changes = thrash["scale_ups"] + thrash["scale_downs"]
    assert 0 < thrash_changes < 6, (
        f"scale-thrash oscillation out of bounds: {thrash_changes} "
        f"membership changes over 6 flapping waves "
        f"(0 = controller never engaged; >=6 = one per wave, thrashing)"
    )
    # join-fail: every mid-join death must roll back AND the post-window
    # retry must land (the fleet ends the run scaled up)
    jf = regimes["join-fail"]["autoscale"]
    assert jf["join_failures"] >= 2 and jf["scale_ups"] >= 1, (
        f"join-fail regime did not exercise the gate: {jf}"
    )
    return {
        "metric": "chaos",
        "value": worst_recovery,
        "unit": "worst_recovery_waves",
        "extra": {
            "seed": seed,
            "regimes": regimes,
            "invariant_violations": violations,
        },
    }


# ---------------------------------------------------------- crash recovery
async def _journal_overhead_ab(args) -> dict:
    """Journal-on vs journal-off A/B through the same scheduler stack
    (obs-overhead discipline: arrival-paced, OFF/ON paired per round,
    min of round medians). The stub decision costs 80 ms — ~3x BELOW the
    measured real-engine raw decision p50 (~233 ms at 1B, BENCH history),
    so the reported percentage over-states production overhead. Binds
    run through the scheduler's BLOCKING-binder path (to_thread — the
    shape every real apiserver binder takes), so the ON arm's per-bind
    fsync (~0.7 ms, default "intent" policy) rides the executor exactly
    where production pays it, instead of serializing the event loop the
    way no deployed binder does."""
    import dataclasses as _dc
    import shutil as _shutil
    import tempfile as _tempfile

    from k8s_llm_scheduler_tpu.engine.backend import StubBackend
    from k8s_llm_scheduler_tpu.sched.client import DecisionClient
    from k8s_llm_scheduler_tpu.sched.journal import DecisionJournal
    from k8s_llm_scheduler_tpu.sched.loop import Scheduler
    from k8s_llm_scheduler_tpu.sched.recovery import JournaledBinder
    from k8s_llm_scheduler_tpu.testing import (
        SCHEDULER_NAME,
        pod_burst,
        synthetic_cluster,
    )

    stub_latency_s = 0.080
    n_pods = 160
    arrival_rate = 50.0

    class _ExecutorBinder:
        # the production-binder shape: KubeCluster's binding POST is
        # blocking, so the scheduler routes it through to_thread — both
        # arms take that path, and the journaled arm's fsync lands on
        # the executor where deployments actually pay it
        bind_is_nonblocking = False

        def __init__(self, inner) -> None:
            self._inner = inner

        def bind_pod_to_node(self, pod_name, namespace, node_name):
            return self._inner.bind_pod_to_node(
                pod_name, namespace, node_name
            )

    async def one_round(tag: str, journal_dir) -> float:
        cluster = synthetic_cluster(args.nodes)
        client = DecisionClient(
            StubBackend(latency_s=stub_latency_s), cache=None,
        )
        binder = _ExecutorBinder(cluster)
        journal = None
        if journal_dir is not None:
            journal = DecisionJournal(journal_dir, fsync_policy="intent")
            binder = JournaledBinder(binder, journal)
        scheduler = Scheduler(
            cluster, binder, client,
            scheduler_name=SCHEDULER_NAME, snapshot_ttl_s=300.0,
            max_concurrency=256, prefix_prewarm_s=0.0,
        )
        task = asyncio.create_task(scheduler.run())
        pods = [
            _dc.replace(p, name=f"{tag}-{p.name}")
            for p in pod_burst(n_pods, distinct_shapes=args.shapes)
        ]
        try:
            latencies, _ = await run_burst(
                scheduler, cluster, pods, timeout_s=300.0,
                arrival_rate=arrival_rate,
            )
        finally:
            scheduler.stop()
            cluster.close()
            await asyncio.wait_for(task, timeout=30)
            if journal is not None:
                journal.close()
        return statistics.median(latencies.values())

    workdir = _tempfile.mkdtemp(prefix="bench-recovery-")
    try:
        await one_round("warm", None)  # warm pools/paths, discarded
        p50s: dict[bool, list[float]] = {False: [], True: []}
        for r in range(args.rounds):
            # OFF then ON inside each round: weather drift cancels in
            # the pair (obs-overhead discipline)
            p50s[False].append(await one_round(f"off{r}", None))
            p50s[True].append(
                await one_round(f"on{r}", f"{workdir}/j{r}")
            )
    finally:
        _shutil.rmtree(workdir, ignore_errors=True)
    p50_off = min(p50s[False])
    p50_on = min(p50s[True])
    overhead_pct = (p50_on - p50_off) / p50_off * 100.0
    return {
        "overhead_pct": round(overhead_pct, 3),
        "p50_journaled_ms": round(p50_on, 3),
        "p50_bare_ms": round(p50_off, 3),
        "round_p50s_off_ms": [round(v, 3) for v in p50s[False]],
        "round_p50s_on_ms": [round(v, 3) for v in p50s[True]],
        "stub_latency_ms": stub_latency_s * 1000.0,
        "fsync_policy": "intent",
        "threshold_pct": 2.0,
        "note": (
            "stub at 80ms/decision (~3x below the measured 1B raw "
            "decision p50) with binds on the blocking/to_thread path "
            "both arms — the percentage over-states production overhead"
        ),
    }


def recovery_bench(args) -> dict:
    """`--preset recovery`: the durable decision plane end to end.

    Runs the three crash regimes (chaos/harness crash mode: a journal-
    backed replica over a file-backed lease store, dropped COLD at
    seeded lifecycle points and rebuilt from disk) and FAILS unless
    every run is invariant-clean with every pod bound exactly once
    ACROSS the restarts — zero lost, zero double-bound, judged by the
    monitor book that spans all process lifetimes. Publishes MTTR per
    restart (waves + ms from the kill to the rebuilt replica's first
    bind, rebuild + journal replay + reconciliation inclusive) and the
    journal's overhead on decision p50 (bar: <2%, same discipline as
    obs-overhead)."""
    from k8s_llm_scheduler_tpu.chaos import run_chaos

    seed = args.seed if args.seed is not None else 0
    regimes = {}
    worst_mttr_ms = 0.0
    worst_mttr_waves = 0
    for regime in (
        "crash-restart", "torn-journal", "crash-during-recovery",
    ):
        report = run_chaos(
            regime, seed=seed, n_waves=8,
            n_nodes=args.nodes, n_pods=args.pods,
        )
        inv = report["invariants"]
        assert inv["clean"], (
            f"{regime}: invariant violations across restarts: "
            + json.dumps(inv["violations"])
        )
        assert report["scores"]["bound_frac"] == 1.0, (
            f"{regime}: lost binds — bound_frac "
            f"{report['scores']['bound_frac']} (unschedulable: "
            f"{report['unschedulable']})"
        )
        restarts = report["restarts"]
        assert restarts, f"{regime}: no cold restart happened"
        for r in restarts:
            if "mttr_ms" in r:
                worst_mttr_ms = max(worst_mttr_ms, r["mttr_ms"])
                worst_mttr_waves = max(worst_mttr_waves, r["mttr_waves"])
        regimes[regime] = {
            "clean": inv["clean"],
            "checks": inv["checks"],
            "plan_digest": report["plan_digest"],
            "restarts": restarts,
            "journal": {
                k: report["journal"][k]
                for k in ("appends", "fsyncs", "open_intents",
                          "torn_bytes_dropped", "counts")
            },
            "bound_frac": report["scores"]["bound_frac"],
            "recovery_waves": report["recovery"]["recovery_waves"],
            "wall_ms": report["wall_ms"],
        }
    overhead = asyncio.run(_journal_overhead_ab(args))
    assert overhead["overhead_pct"] < 2.0, (
        f"journal overhead {overhead['overhead_pct']:.2f}% >= 2% of "
        f"decision p50 (journaled {overhead['p50_journaled_ms']:.3f}ms "
        f"vs bare {overhead['p50_bare_ms']:.3f}ms)"
    )
    return {
        "metric": "recovery",
        "value": round(worst_mttr_ms, 3),
        "unit": "worst_mttr_ms",
        "extra": {
            "seed": seed,
            "worst_mttr_waves": worst_mttr_waves,
            "regimes": regimes,
            "journal_overhead": overhead,
            "lost_binds": 0,
            "double_binds": 0,
        },
    }


# ------------------------------------------------------------- learn loop
def learn_bench(args) -> dict:
    """`--preset learn`: the closed policy-improvement loop end to end on
    a micro REAL engine (f32, 2 layers — the test_rollout scale that
    compiles in seconds on CPU).

    The incumbent is a PUBLISHED random-init checkpoint served greedily
    through the real constrained-decode stack. One LearnLoop cycle mines
    its losses against the spread-lookahead teacher into the incident
    corpus, finetunes FROM the incumbent params on the reconstructed
    incident cases (mixed with base-distribution replay), publishes the
    candidate with lineage, and gates it two-sided. The preset FAILS
    unless: the candidate strictly beats the incumbent on the mined
    weakness cases, the base-arena gate passes within tolerance, the
    promotion hot-swaps through the live HotSwapper path, and the
    recorded learn trace replays byte-identically."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from k8s_llm_scheduler_tpu.engine.local import build_local_backend
    from k8s_llm_scheduler_tpu.engine.tokenizer import build_builtin_tokenizer
    from k8s_llm_scheduler_tpu.learn import (
        IncidentCorpus,
        LearnConfig,
        LearnLoop,
        backend_decide,
        decide_policy_arm,
        save_learn_trace,
        verify_learn_trace,
    )
    from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
    from k8s_llm_scheduler_tpu.models.llama import init_params
    from k8s_llm_scheduler_tpu.models.loader import save_checkpoint
    from k8s_llm_scheduler_tpu.rollout import (
        CheckpointRegistry,
        GateConfig,
        HotSwapper,
        run_gate,
    )

    seed = args.seed if args.seed is not None else 0
    steps = int(getattr(args, "learn_steps", None) or 300)
    base_cfg = LlamaConfig(
        name="learn-micro", vocab_size=512, d_model=64, n_layers=2,
        n_heads=2, n_kv_heads=1, d_ff=128, max_seq_len=4096,
        rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
    )
    tokenizer_name = "numeric"
    _tok, model_cfg = build_builtin_tokenizer(tokenizer_name, base_cfg)
    work = Path(tempfile.mkdtemp(prefix="bench-learn-"))

    def make_backend(checkpoint_path):
        return build_local_backend(
            cfg=model_cfg,
            checkpoint_path=str(checkpoint_path),
            tokenizer_name=tokenizer_name,
            temperature=0.0,  # the arena/trace determinism contract
            max_slots=4, num_pages=128, page_size=64,
            max_pages_per_seq=32,
            prefill_buckets=(256, 512, 1024, 2048),
            chunk_steps=4,
        )

    try:
        registry = CheckpointRegistry(work / "registry")
        corpus = IncidentCorpus(work / "corpus")
        incumbent_dir = work / "incumbent"
        save_checkpoint(
            incumbent_dir, init_params(jax.random.PRNGKey(seed + 1), model_cfg)
        )
        incumbent = registry.publish(
            incumbent_dir, cfg=model_cfg, tokenizer=tokenizer_name,
            note="bench incumbent (random-init)",
        )
        registry.set_active(incumbent.version)
        incumbent_ckpt = registry.get(incumbent.version).checkpoint_path

        incumbent_backend = make_backend(incumbent_ckpt)
        incumbent_decide = backend_decide(incumbent_backend)
        gate_cfg = GateConfig(
            seed=seed, nodes=8, pods=24, shapes=6, waves=2,
            spread_tolerance=0.05, wave_timeout_s=300.0,
        )
        learn_cfg = LearnConfig(
            seed=seed,
            mine_seeds=(seed, seed + 1),
            mine_nodes=args.nodes, mine_pods=args.pods,
            mine_shapes=args.shapes, mine_waves=3,
            replay_fraction=0.25,
            steps=steps, batch_size=8, seq_len=1536, lr=1e-3,
            weakness_cases=24,
            gate=gate_cfg,
        )

        def candidate_decide_factory(checkpoint_dir):
            backend = make_backend(checkpoint_dir)
            return backend_decide(backend), backend.close

        loop = LearnLoop(
            registry, corpus, learn_cfg,
            # mining + weakness use the greedy real engine as a policy arm
            # (sequential deterministic replay — the model is the thing
            # under test, not the wire plumbing the arena preset covers)
            mine_arm_factory=lambda: decide_policy_arm(
                "llm", incumbent_decide
            ),
            incumbent_decide_factory=lambda: (
                incumbent_decide, lambda: None
            ),
            candidate_decide_factory=candidate_decide_factory,
            gate_runner=lambda version: run_gate(
                lambda: make_backend(incumbent_ckpt),
                lambda: make_backend(
                    registry.get(version).checkpoint_path
                ),
                gate_cfg,
            ),
            model_cfg=model_cfg,
            tokenizer_name=tokenizer_name,
            swapper=HotSwapper(
                incumbent_backend, registry, model_cfg,
                mesh=incumbent_backend.engine.mesh,
            ),
        )
        t0 = time.perf_counter()
        report = loop.run_cycle(work / "cycle", note="bench learn")
        cycle_s = time.perf_counter() - t0

        trace_path = work / "learn-trace.json"
        save_learn_trace(report, trace_path)
        replay_ok, replay_detail = verify_learn_trace(trace_path)
        incumbent_backend.close()

        inc_score = report["weakness"]["incumbent"]["score"]
        cand_score = report["weakness"]["candidate"]["score"]
        assert report["action"] == "promoted", (
            f"learn cycle did not promote: weakness {inc_score} -> "
            f"{cand_score}, gate {report['gate']}"
        )
        assert cand_score > inc_score, (
            f"promoted checkpoint does not strictly improve the mined-"
            f"weakness score: {inc_score} -> {cand_score}"
        )
        assert report["gate"]["pass"], report["gate"]
        assert replay_ok, f"learn trace replay diverged: {replay_detail}"
        assert registry.active() == report["candidate_version"]

        return {
            "metric": "learn_loop",
            "value": round(cand_score - inc_score, 6),
            "unit": "weakness_score_gain",
            "extra": {
                "seed": seed,
                "steps": steps,
                "action": report["action"],
                "weakness_incumbent": inc_score,
                "weakness_candidate": cand_score,
                "per_class": report["per_class"],
                "corpus_version": report["corpus_version"],
                "corpus_digest": report["corpus_digest"],
                "incumbent_version": report["incumbent_version"],
                "candidate_version": report["candidate_version"],
                "gate_checks": report["gate"]["checks"],
                "train_loss": report["train_loss"],
                "swap_pause_s": report.get("swap", {}).get("pause_s"),
                "trace_replay": replay_detail,
                "cycle_s": round(cycle_s, 1),
                "model": "learn-micro (random-init incumbent)",
            },
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)


# ------------------------------------------------------- model throughput/MFU
class _FleetSimBackend:
    """One simulated TPU decision service per fleet replica: decisions
    SERIALIZE behind an asyncio lock (the device only runs one wave at a
    time) and cost `service_s` each; the pick itself is the stub's
    resource-balanced choice so placements stay legal. The sim backend
    is the point of the preset: decisions/s must scale with replica
    count because each replica brings its own device, not because the
    host got lucky."""

    def __init__(self, service_s: float = 0.02) -> None:
        import itertools

        self.service_s = service_s
        self._rr = itertools.count()
        self._ready_memo: tuple[int, list] | None = None
        # created lazily ON the running loop (the backend is constructed
        # before the bench's asyncio.run); only loop-thread coroutines
        # touch it afterwards
        self._alock: "asyncio.Lock | None" = None

    def _pick(self, pod, nodes):
        """O(1) round-robin over ready nodes. NOT the stub's 500-node
        feasibility scan: that scan is host compute the real engine
        doesn't pay per decision, and at fleet scale it serialized on
        the shared event loop and masked the device-time scaling this
        preset measures (the preset's pods are unconstrained, so every
        ready node is legal). The ready list is memoized per snapshot
        object — one scan per burst, not per pod."""
        from k8s_llm_scheduler_tpu.types import (
            DecisionSource,
            SchedulingDecision,
        )

        memo = self._ready_memo
        if memo is None or memo[0] != id(nodes):
            memo = (id(nodes), [n for n in nodes if n.is_ready])
            self._ready_memo = memo
        ready = memo[1]
        node = ready[next(self._rr) % len(ready)]
        return SchedulingDecision(
            selected_node=node.name,
            confidence=0.9,
            reasoning="fleet-sim round robin",
            source=DecisionSource.LLM,
            latency_ms=self.service_s * 1000.0,
        )

    async def get_scheduling_decision_async(self, pod, nodes, work="prefill"):
        if self._alock is None:
            self._alock = asyncio.Lock()
        async with self._alock:
            await asyncio.sleep(self.service_s)
        return self._pick(pod, nodes)

    def get_scheduling_decision(self, pod, nodes, work="prefill"):
        time.sleep(self.service_s)
        return self._pick(pod, nodes)


async def _fleet_round(
    n_replicas: int, n_pods: int, n_nodes: int, service_s: float,
    timeout_s: float = 300.0,
) -> dict:
    """One replica-count data point: burst n_pods distinct-shape pods at
    a fresh fleet, measure decisions/s and release->bind latency."""
    from k8s_llm_scheduler_tpu.cluster.fake import FakeCluster
    from k8s_llm_scheduler_tpu.cluster.interface import RawPod
    from k8s_llm_scheduler_tpu.fleet import Fleet

    scheduler_name = "ai-llama-scheduler"
    cluster = FakeCluster()
    cluster.add_nodes(n_nodes, prefix="fleet-node")
    # every pod its own resource shape -> every decision is a leader
    # (the cache key digests the shape, not the name — core/cache.py)
    for i in range(n_pods):
        cluster.add_pod(RawPod(
            name=f"fleet-pod-{i:05d}",
            namespace="default",
            scheduler_name=scheduler_name,
            container_requests=(
                {"cpu": f"{100 + i}m", "memory": "128Mi"},
            ),
        ))
    fleet = Fleet(
        cluster, cluster, lambda i: _FleetSimBackend(service_s),
        n_replicas=n_replicas,
        n_shards=32,
        scheduler_name=scheduler_name,
        lease_ttl_s=3600.0,       # no failover here: pure throughput
        snapshot_ttl_s=1e9,       # one burst, one snapshot per replica
        list_pending=lambda: cluster.pending_pods(scheduler_name),
    )
    bind_times: list[float] = []
    for replica in fleet.replicas:
        orig = replica.scheduler._note_bind

        def tagging_note(ok, pod, decision, _orig=orig):
            if ok:
                bind_times.append(time.perf_counter())
            _orig(ok, pod, decision)

        replica.scheduler._note_bind = tagging_note

    t0 = time.perf_counter()
    await fleet.start(lease_threads=False)
    deadline = t0 + timeout_s
    telemetry = None
    try:
        while time.perf_counter() < deadline:
            if fleet.get_stats()["total_scheduled"] >= n_pods:
                break
            await asyncio.sleep(0.02)
        stats = fleet.get_stats()
        # Merged-telemetry extras (observability/fleetview.py): fleet p99
        # from MERGED histogram buckets vs the max per-replica p99 — the
        # aggregation the 16-replica production view rests on, exercised
        # on every bench run.
        agg = fleet.aggregator(include_traces=False)
        agg.pull_all()
        fleet_pct = agg.fleet_percentiles("decide")
        per_replica_p99 = [
            (r.get("phases", {}).get("decide") or {}).get("p99_ms", 0.0)
            for r in stats["replicas"]
        ]
        if fleet_pct is not None:
            telemetry = {
                "fleet_decide_p50_ms": fleet_pct["p50_ms"],
                "fleet_decide_p99_ms": fleet_pct["p99_ms"],
                "fleet_decide_count": fleet_pct["count"],
                "max_replica_decide_p99_ms": max(per_replica_p99),
            }
    finally:
        await fleet.stop()
    if stats["total_scheduled"] < n_pods:
        raise RuntimeError(
            f"fleet round ({n_replicas} replicas) bound only "
            f"{stats['total_scheduled']}/{n_pods} pods in {timeout_s}s"
        )
    if cluster.bind_count != n_pods or stats["failed_bindings"]:
        raise RuntimeError(
            f"fleet round bind accounting broken: bind_count="
            f"{cluster.bind_count}, failed={stats['failed_bindings']}"
        )
    wall_s = max(bind_times) - t0
    lat = sorted((t - t0) * 1000.0 for t in bind_times)
    out = {
        "replicas": n_replicas,
        "decisions_per_s": round(n_pods / wall_s, 1),
        "wall_s": round(wall_s, 3),
        "bind_p50_ms": round(lat[len(lat) // 2], 3),
        "bind_p99_ms": round(lat[min(len(lat) - 1, int(len(lat) * 0.99))], 3),
        "fenced_binds": stats["fenced_binds"],
        "l2": {
            k: stats["l2"][k] for k in ("hits", "misses", "generation")
        },
    }
    if telemetry is not None:
        # sanity: a mixture's p-quantile never exceeds the max component
        # p-quantile, and the shared bucket ladder preserves that in
        # bucket space — a violation means the merge mixed ladders
        assert (
            telemetry["fleet_decide_p99_ms"]
            <= telemetry["max_replica_decide_p99_ms"] * 1.0001
        ), telemetry
        out["merged_telemetry"] = telemetry
    return out


def _kvplane_flatness(
    pin_tokens: int,
    *,
    replica_counts=(1, 4, 16),
    snapshots: int = 2,
    decisions: int = 600,
) -> dict:
    """FLEET-WIDE snapshot prefill tokens per decision vs replica count,
    shared prefix-KV plane on and off — token-count-exact over a real
    KVPlaneStore driving model-free StubPinEngines (the protocol, not
    the model, decides who prefills; the token arithmetic is exact
    either way, the _snapshot_token_table discipline).

    The workload is FIXED: one fleet serves `decisions` decisions over
    `snapshots` pinned snapshots of `pin_tokens` tokens each, sharded
    across n replicas. Plane OFF, every replica pins every snapshot
    itself — fleet prefill grows linearly in n (the 16x waste ISSUE 17
    names). Plane ON, one elected filler prefills each snapshot and the
    rest adopt — fleet prefill is ~flat in n (ROADMAP item 3's bar)."""
    from k8s_llm_scheduler_tpu.fleet.kvplane import (
        KVPlaneClient,
        KVPlaneStore,
        StubPinEngine,
    )

    points = {}
    for n in replica_counts:
        row = {}
        for arm in ("on", "off"):
            engines = [StubPinEngine() for _ in range(n)]
            clients = None
            if arm == "on":
                store = KVPlaneStore(max_entries=snapshots + 1)
                clients = [
                    KVPlaneClient(store, e, replica=f"replica-{i}")
                    for i, e in enumerate(engines)
                ]
            for s in range(snapshots):
                ids = [5000 + s * 97 + j for j in range(pin_tokens)]
                for i in range(n):
                    if clients is not None:
                        clients[i].pin(ids)
                    else:
                        engines[i].pin_prefix(ids)
            fleet_tokens = sum(
                e.stats["prefill_tokens"] for e in engines
            )
            row[arm] = {
                "fleet_prefill_tokens": fleet_tokens,
                "fleet_prefill_tokens_per_decision": round(
                    fleet_tokens / decisions, 2
                ),
            }
        points[str(n)] = row
    lo, hi = str(replica_counts[0]), str(replica_counts[-1])
    on_lo = points[lo]["on"]["fleet_prefill_tokens"]
    on_hi = points[hi]["on"]["fleet_prefill_tokens"]
    off_hi = points[hi]["off"]["fleet_prefill_tokens"]
    return {
        "pin_tokens": pin_tokens,
        "snapshots": snapshots,
        "decisions": decisions,
        "replica_points": points,
        # the acceptance bar: plane-on fleet prefill does not grow with
        # replica count (every snapshot prefilled exactly once)
        "flat_1_to_16": on_hi == on_lo,
        "dedup_ratio_at_16": round(off_hi / on_hi, 2) if on_hi else None,
    }


async def fleet_bench(args) -> dict:
    """`--preset fleet`: decisions/s scaling across sharded scheduler
    replicas (fleet/frontend.py) over the sim backend. Acceptance bar
    (ISSUE 6): 4 replicas >= 2.5x the decisions/s of 1 replica, zero
    failed/double binds at every count. The kvplane extra (ISSUE 17)
    adds the shared prefix-KV plane's bar: fleet-wide snapshot prefill
    tokens/decision ~flat from 1 to 16 replicas with the plane on."""
    service_s = 0.02
    points = {}
    for n in (1, 4, 16):
        points[str(n)] = await _fleet_round(
            n, args.pods, args.nodes, service_s
        )
    d1 = points["1"]["decisions_per_s"]
    d4 = points["4"]["decisions_per_s"]
    d16 = points["16"]["decisions_per_s"]
    speedup_4v1 = round(d4 / d1, 2)
    # token-count-exact at this preset's node count (the fleet rounds
    # run on sim decision services, no engine)
    token_row = _snapshot_token_table((args.nodes,))[0]
    return {
        "metric": "fleet_decisions_per_s",
        "value": d4,
        "unit": "decisions/s@4replicas",
        "extra": {
            "pods": args.pods,
            "nodes": args.nodes,
            "sim_service_ms": service_s * 1000.0,
            "replica_points": points,
            "speedup_4v1": speedup_4v1,
            "speedup_16v1": round(d16 / d1, 2),
            "meets_bar_4v1_ge_2.5x": speedup_4v1 >= 2.5,
            # what the delta-encoded admission plane pays vs a
            # whole-prompt render (see --preset burst for the measured
            # engine-side figure)
            "prefill_tokens_per_decision": token_row,
            # shared prefix-KV plane: the pinned snapshot prefix is the
            # whole-prompt render above; with the plane on, ONE replica
            # prefills it per snapshot generation, fleet-wide
            "kvplane": _kvplane_flatness(
                token_row["whole_prefix_tokens"], decisions=args.pods
            ),
        },
    }


# ------------------------------------------------------------- autoscale
async def _autoscale_arm(
    scenario, *, elastic: bool, n_static: int = 1, max_replicas: int = 8,
    service_ms: float = 20.0, threshold_ms: float = 200.0,
    tick_s: float = 1.0, timeout_s: float = 120.0,
) -> dict:
    """One frontier arm: replay the diurnal scenario's waves through a
    REAL fleet (elastic: AutoscaleController over Fleet.start_join/
    remove_replica; static: fixed N). Binds are real (exactly-once
    accounting); per-pod latency is MODELED from queue position over the
    serving replica count — ceil-position x service time — so the SLO
    axis is deterministic and identical in structure across arms."""
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

    scheduler_name = "ai-llama-scheduler"
    cluster = FakeCluster()
    for n in scenario.nodes:
        cluster.add_node(FakeNode(
            name=n.name,
            cpu_capacity_cores=n.cpu_cores,
            memory_capacity_gb=n.memory_gb,
            max_pods=n.max_pods,
            labels=dict(n.labels),
            taints=n.taints,
            ready=n.ready,
        ))
    clock = _VirtualClock()
    fleet = Fleet(
        cluster, cluster, lambda i: HashPlacementBackend(),
        n_replicas=1 if elastic else n_static,
        n_shards=2 * max(max_replicas, n_static),
        scheduler_name=scheduler_name,
        lease_ttl_s=6 * tick_s, clock=clock,
        snapshot_ttl_s=1e9,
        list_pending=lambda: cluster.pending_pods(scheduler_name),
    )
    bound: set[str] = set()

    def tap_replica(replica) -> None:
        orig = replica.scheduler._note_bind

        def tagging_note(ok, pod, decision, _orig=orig):
            if ok:
                bound.add(pod.name)
            _orig(ok, pod, decision)

        replica.scheduler._note_bind = tagging_note

    fleet.on_replica_start = tap_replica
    for replica in fleet.replicas:
        tap_replica(replica)

    wave_state = {"i": 0, "incoming": 0}
    controller = None
    if elastic:
        controller = AutoscaleController(
            fleet,
            AutoscaleConfig(
                min_replicas=1, max_replicas=max_replicas,
                target_per_replica=8.0, target_utilization=0.75,
                up_threshold=1.0, down_threshold=0.5,
                max_step=2,
                up_cooldown_s=tick_s,       # one join per wave max
                down_cooldown_s=3 * tick_s,
                join_budget_ticks=3, join_backoff_ticks=1,
                max_join_retries=3, split_enabled=False,
            ),
            queue_depth_fn=lambda: wave_state["incoming"],
            clock=lambda: wave_state["i"] * tick_s,
        )

    def serving_replicas() -> int:
        return max(
            1, sum(1 for r in fleet.replicas if r.manager.owned())
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
            coros.extend(replica.scheduler.schedule_pod(p) for p in todo)
        return coros

    async def drain(released: set[str]) -> None:
        deadline = time.perf_counter() + timeout_s
        stalls = 0
        while released - bound:
            if time.perf_counter() > deadline:
                raise RuntimeError(
                    f"autoscale arm: {len(released - bound)} pods never "
                    f"bound (wave {wave_state['i']})"
                )
            await asyncio.sleep(0.01)
            stalls += 1
            if stalls % 25 == 0:
                fleet.tick_leases()
                coros = reoffer()
                if coros:
                    await asyncio.gather(*coros, return_exceptions=True)

    capacity_per_replica = int(threshold_ms // service_ms)
    violations = 0
    replica_seconds = 0.0
    per_wave: list[dict] = []
    await fleet.start(lease_threads=False)
    try:
        for wave_idx, wave in enumerate(scenario.waves):
            clock.advance(tick_s)
            fleet.tick_leases()
            wave_state["i"] = wave_idx + 1
            wave_state["incoming"] = len(wave)
            if controller is not None:
                await controller.tick()
            serving = serving_replicas()
            replica_seconds += serving * tick_s
            w = len(wave)
            wave_viol = max(0, w - serving * capacity_per_replica)
            violations += wave_viol
            per_wave.append({
                "wave": wave_idx, "pods": w, "replicas": serving,
                "violations": wave_viol,
            })
            if not wave:
                continue
            for pod in wave:
                cluster.add_pod(pod.to_raw_pod())
            await drain({p.name for p in wave})
        n_pods = scenario.n_pods
        # zero dropped / zero double-bound across every scale event:
        # every pod observed bound exactly once, and the cluster's own
        # bind book agrees (a double bind would either fail loudly there
        # or inflate bind_count past the pod count)
        assert len(bound) == n_pods, (
            f"dropped pods: {n_pods - len(bound)}"
        )
        assert cluster.bind_count == n_pods, (
            f"bind_count {cluster.bind_count} != {n_pods} pods "
            "(double or lost bind)"
        )
        stats = fleet.get_stats()
        out = {
            "arm": "elastic" if elastic else f"static-{n_static}",
            "slo_violations": violations,
            "slo_violation_frac": round(violations / n_pods, 6),
            "replica_seconds": round(replica_seconds, 1),
            "final_replicas": fleet.n_live,
            "fenced_binds": stats["fenced_binds"],
            "failed_bindings": stats["failed_bindings"],
        }
        if controller is not None:
            out["scale"] = {
                k: controller.counters[k]
                for k in ("scale_ups", "scale_downs", "join_failures")
            }
            out["scale_events"] = len(controller.scale_events())
            out["peak_replicas"] = max(p["replicas"] for p in per_wave)
        out["per_wave"] = per_wave
        return out
    finally:
        await fleet.stop()


async def autoscale_bench(args) -> dict:
    """`--preset autoscale`: the SLO-burn-vs-replica-seconds frontier.

    One seeded diurnal arrival curve (sim/scenarios arrival="diurnal")
    replayed through an ELASTIC fleet and static-N baselines. The
    elastic arm must DOMINATE at least one static arm on BOTH axes
    (<= on both, strictly better on one): over-provisioning (static at
    peak size) burns replica-seconds all day, under-provisioning burns
    the SLO budget at peak — the control loop must beat at least one of
    those corners outright, or it is not earning its complexity."""
    from k8s_llm_scheduler_tpu.sim.scenarios import (
        ScenarioSpec,
        generate_scenario,
    )

    seed = args.seed if args.seed is not None else 0
    spec = ScenarioSpec(
        name="autoscale-diurnal",
        seed=seed,
        n_nodes=args.nodes,
        n_pods=args.pods,
        shapes=args.shapes,
        arrival="diurnal",
        n_waves=24,
        diurnal_amplitude=0.9,
        hetero=True,
        constraint_mix=("uniform",),
    )
    scenario = generate_scenario(spec)
    max_replicas = 8
    arms = {}
    arms["elastic"] = await _autoscale_arm(
        scenario, elastic=True, max_replicas=max_replicas
    )
    for n in (2, 4, max_replicas):
        arm = await _autoscale_arm(scenario, elastic=False, n_static=n)
        arms[arm["arm"]] = arm

    elastic = arms["elastic"]
    dominated = [
        name for name, arm in arms.items()
        if name != "elastic"
        and elastic["slo_violation_frac"] <= arm["slo_violation_frac"]
        and elastic["replica_seconds"] <= arm["replica_seconds"]
        and (
            elastic["slo_violation_frac"] < arm["slo_violation_frac"]
            or elastic["replica_seconds"] < arm["replica_seconds"]
        )
    ]
    assert dominated, (
        "elastic arm dominates no static arm — frontier: "
        + json.dumps({
            name: {
                "burn": arm["slo_violation_frac"],
                "replica_seconds": arm["replica_seconds"],
            }
            for name, arm in arms.items()
        })
    )
    static_peak = arms[f"static-{max_replicas}"]
    frontier = {
        name: {
            "slo_violation_frac": arm["slo_violation_frac"],
            "replica_seconds": arm["replica_seconds"],
        }
        for name, arm in arms.items()
    }
    return {
        "metric": "autoscale_frontier",
        # headline: elastic cost as a fraction of peak static provisioning
        # (same curve, zero-drop, SLO no worse than the dominated arm)
        "value": round(
            elastic["replica_seconds"] / static_peak["replica_seconds"], 3
        ),
        "unit": f"replica_seconds_vs_static{max_replicas}",
        "extra": {
            "seed": seed,
            "pods": args.pods,
            "nodes": args.nodes,
            "waves": 24,
            "diurnal_amplitude": 0.9,
            "service_ms": 20.0,
            "threshold_ms": 200.0,
            "frontier": frontier,
            "dominated_arms": dominated,
            "arms": {
                name: {k: v for k, v in arm.items() if k != "per_wave"}
                for name, arm in arms.items()
            },
            "elastic_wave_trajectory": [
                (p["wave"], p["pods"], p["replicas"])
                for p in elastic["per_wave"]
            ],
        },
    }


def _synthetic_text(seed: int, n_tokens: int) -> str:
    """Deterministic ASCII filler, distinct per seed from the first byte
    (so prefix prefills never LCP-seed off each other)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    body = rng.integers(ord("a"), ord("z") + 1, size=n_tokens - 8, dtype=np.uint8)
    return f"[seed {seed}]" + bytes(body).decode("ascii")


def model_throughput(
    model: str,
    quantize: str | None,
    peak_override: float | None,
    slots: int = 16,
    decode_matmul: str = "dense",
    params=None,
) -> dict:
    """Engine-level microbench: prefill tok/s, pipelined decision-wave decode
    tok/s + decisions/s, and MFU against the chip's peak bf16 FLOP/s.

    Bypasses the scheduler loop: this measures the MODEL path (the thing that
    scales with model size), not cache hits or asyncio. Random-init weights,
    byte tokenizer — tokenization does not change the math.
    """
    import jax
    import numpy as np

    from k8s_llm_scheduler_tpu.engine.constrained import build_decision_dfa
    from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
    from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer
    from k8s_llm_scheduler_tpu.models.llama import init_params

    cfg = build_cfg(model)
    tok = ByteTokenizer(vocab_size=max(512, cfg.vocab_size))
    peak_tflops, device_kind = detect_peak_tflops(peak_override)

    if params is None:
        # `params` lets an A/B harness (tools/ab_decode.py) share ONE set
        # of weights across impl variants in one process — separate
        # runs differ in device state and host load (8B init alone is
        # ~minutes per run).
        if quantize == "int8":
            from k8s_llm_scheduler_tpu.models.quant import init_params_int8_host

            params = init_params_int8_host(0, cfg)
        else:
            params = init_params(jax.random.PRNGKey(0), cfg)

    prefill_n = 4000
    eng = InferenceEngine(
        params, cfg, tok,
        num_pages=64, page_size=128, max_slots=slots, max_pages_per_seq=16,
        # Fine bucket ladder (like the presets', capped at 4096 — the
        # microbench's longest prompt is the 4000-token prefill): a
        # 250-token suffix rides the 256 bucket. Wave time is dominated by
        # the R x bucket suffix prefill, so the old 512 floor UNDERSTATED
        # decode throughput ~35% (1B: 43.6 -> 66.4 decisions/s measured
        # when 250-token suffixes stopped padding to 512).
        prefill_buckets=(128, 256, 512, 1024, 2048, 4096),
        chunk_steps=8, prefix_chunk=2048,
        temperature=0.0,
        decode_matmul=decode_matmul,
    )
    # prefix_chunk 2048 routes the 4000-token prefill through the chunked
    # cascade (flash prefix kernel): measured 23% faster than single-shot
    # at 1B (MFU 0.28 -> 0.34) and it is the path long prompts actually take.

    # Tiny jitted probe: device_get of one element forces the whole queued
    # program chain to complete WITHOUT copying the multi-GB KV to the
    # host.
    probe = jax.jit(lambda a: a[0, :1, 0, 0])

    def sync_prefix():
        jax.device_get(probe(eng._prefix.k))

    # --- prefill: K back-to-back 4000-token single-shot prefills (bucket
    # 4096), one sync at the end — one dispatch round trip over eight.
    n_prefills = 8
    eng.set_prefix(tok.encode(_synthetic_text(1, prefill_n)))  # compiles
    sync_prefix()  # also compiles the probe
    t0 = time.perf_counter()
    for i in range(n_prefills):
        eng.set_prefix(tok.encode(_synthetic_text(2 + i, prefill_n)))
    sync_prefix()
    prefill_dt = (time.perf_counter() - t0) / n_prefills
    prefill_tps = prefill_n / prefill_dt
    # prefill attends causally: average context = n/2
    prefill_flops = prefill_n * (
        matmul_flops_per_token(cfg) + attn_flops_per_token(cfg, prefill_n / 2)
    )

    # --- decision waves: 16 distinct pod suffixes, 6 waves pipelined.
    names = [f"bench-node-{i:03d}" for i in range(32)]
    eng.set_grammar(build_decision_dfa(tok, names, max_reason_tokens=60))
    suffixes = [
        tok.encode(_synthetic_text(100 + i, 250)) for i in range(slots)
    ]
    eng.decide_wave(suffixes, max_new_tokens=72)  # compile + warm
    n_waves = 6
    c0 = dict(eng.stats)
    t0 = time.perf_counter()
    handles = [eng.submit_wave(suffixes, max_new_tokens=72) for _ in range(n_waves)]
    finished = [f for h in handles for f in eng.harvest_wave(h)]
    decode_dt = time.perf_counter() - t0
    decisions = len(finished)
    decode_tokens = eng.stats["decode_tokens"] - c0.get("decode_tokens", 0)
    model_calls = eng.stats["wave_model_calls"] - c0.get("wave_model_calls", 0)
    ctx = eng.prefix_len + 250 + 36  # prefix + suffix + half the emission
    decode_flops = decode_tokens * (
        matmul_flops_per_token(cfg) + attn_flops_per_token(cfg, ctx)
    )
    assert all(f.token_ids for f in finished), "empty decision in throughput bench"

    out = {
        "metric": "model_throughput",
        "value": round(decode_tokens / decode_dt, 1),
        "unit": "decode_tok_per_s",
        "extra": {
            "model": model,
            "weights": "random-init",  # architecture at random init
            "quantize": quantize,
            # the EFFECTIVE impl (now equal to the requested one — the
            # engine refuses ragged on tp>1 meshes at build time rather
            # than silently serving dense under a "ragged" label)
            "decode_matmul": eng.decode_matmul,
            "slots": slots,
            "params_m": round(param_count(cfg) / 1e6, 1),
            "device_kind": device_kind,
            "prefill_tok_per_s": round(prefill_tps, 1),
            "prefill_ms": round(prefill_dt * 1000.0, 2),
            "decisions_per_s": round(decisions / decode_dt, 2),
            # throughput-derived mean wall time per pipelined wave (NOT a
            # per-decision latency percentile — all waves are in flight at
            # once); wave_latency_ms is the first wave's real submit->done.
            "wave_avg_ms": round(decode_dt / n_waves * 1000.0, 2),
            "wave_latency_ms": round(finished[0].latency_ms, 2),
            "decode_tok_per_s": round(decode_tokens / decode_dt, 1),
            "wave_model_calls": model_calls,
            "decode_tokens": decode_tokens,
        },
    }
    if peak_tflops:
        peak = peak_tflops * 1e12
        out["extra"]["mfu_prefill"] = round(prefill_flops / prefill_dt / peak, 4)
        out["extra"]["mfu_decode"] = round(decode_flops / decode_dt / peak, 4)
        out["extra"]["peak_bf16_tflops"] = peak_tflops
    del eng, params
    return out


# ------------------------------------------------- tp serving plane (GSPMD)
def tp_serving_bench(args) -> dict:
    """`--preset tp-serving`: decisions/s + MFU table for the sharded
    serving plane (engine/sharded/) at tp = 1/2/4/8.

    Every point builds a FRESH engine from the same seed: params placed
    via serving_param_specs + shard_params, paged/pinned KV
    head-sharded, and the full serving path — prefix prefill, grammar
    build, packed-wave admission, fused on-device decode — running
    under the mesh. tp=1 is the unsharded engine (mesh=None), the
    single-device baseline the sharded rows are read against.

    MFU divides by tp x per-chip peak: the sharded program owns tp
    chips, so perfect scaling holds MFU flat while decode tok/s grows.
    On a host-device mesh there is no published peak (mfu omitted,
    host_device_mesh recorded) and the table's load-bearing column is
    the greedy token digest — byte-identical emissions across every tp
    layout, the same contract tests/test_sharded.py pins at micro
    scale."""
    import hashlib

    import jax

    from k8s_llm_scheduler_tpu.engine.constrained import build_decision_dfa
    from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
    from k8s_llm_scheduler_tpu.engine.sharded import serving_param_specs
    from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer
    from k8s_llm_scheduler_tpu.models.llama import init_params
    from k8s_llm_scheduler_tpu.parallel.mesh import make_mesh
    from k8s_llm_scheduler_tpu.parallel.sharding import shard_params

    cfg = build_cfg("bench-tp")
    tok = ByteTokenizer(vocab_size=max(512, cfg.vocab_size))
    peak_tflops, device_kind = detect_peak_tflops(args.peak_tflops)
    n_dev = jax.device_count()
    host_mesh = jax.devices()[0].platform != "tpu"

    slots = args.slots or 8
    max_new = args.max_new_tokens or 48
    n_waves = max(1, args.rounds or 2)
    prefill_n = 1024
    suffix_n = 200
    names = [f"bench-node-{i:03d}" for i in range(16)]

    rows = []
    digests: list[str] = []
    for tp in (1, 2, 4, 8):
        if tp > n_dev:
            rows.append({"tp": tp, "skipped": f"only {n_dev} devices"})
            continue
        mesh = make_mesh({"tp": tp}) if tp > 1 else None
        params = init_params(jax.random.PRNGKey(0), cfg)
        if mesh is not None:
            params = shard_params(params, mesh, serving_param_specs(cfg))
        eng = InferenceEngine(
            params, cfg, tok,
            num_pages=64, page_size=64, max_slots=slots,
            max_pages_per_seq=16,
            prefill_buckets=(128, 256, 512, 1024),
            chunk_steps=8, prefix_chunk=512,
            temperature=0.0, mesh=mesh,
        )
        # Tiny jitted probe forces the queued chain without fetching the
        # KV (model_throughput's sync idiom).
        probe = jax.jit(lambda a: a[0, :1, 0, 0])

        def sync_prefix():
            jax.device_get(probe(eng._prefix.k))

        eng.set_prefix(tok.encode(_synthetic_text(1, prefill_n)))  # compiles
        sync_prefix()
        n_prefills = 2
        t0 = time.perf_counter()
        for i in range(n_prefills):
            eng.set_prefix(tok.encode(_synthetic_text(2 + i, prefill_n)))
        sync_prefix()
        prefill_dt = (time.perf_counter() - t0) / n_prefills
        prefill_flops = prefill_n * (
            matmul_flops_per_token(cfg) + attn_flops_per_token(cfg, prefill_n / 2)
        )

        eng.set_grammar(build_decision_dfa(tok, names, max_reason_tokens=40))
        suffixes = [
            tok.encode(_synthetic_text(100 + i, suffix_n)) for i in range(slots)
        ]
        eng.decide_wave(suffixes, max_new_tokens=max_new)  # compile + warm
        c0 = dict(eng.stats)
        t0 = time.perf_counter()
        handles = [
            eng.submit_wave(suffixes, max_new_tokens=max_new)
            for _ in range(n_waves)
        ]
        finished = [f for h in handles for f in eng.harvest_wave(h)]
        decode_dt = time.perf_counter() - t0
        decode_tokens = eng.stats["decode_tokens"] - c0.get("decode_tokens", 0)
        ctx = eng.prefix_len + suffix_n + max_new // 2
        decode_flops = decode_tokens * (
            matmul_flops_per_token(cfg) + attn_flops_per_token(cfg, ctx)
        )
        assert all(f.token_ids for f in finished), f"empty decision at tp={tp}"
        # Order-independent digest of every emitted token sequence: the
        # cross-tp identity column (greedy + deterministic grammar, so
        # every layout must emit the same bytes).
        digest = hashlib.sha256(
            json.dumps(sorted(list(f.token_ids) for f in finished)).encode()
        ).hexdigest()[:16]
        digests.append(digest)

        row = {
            "tp": tp,
            "decisions_per_s": round(len(finished) / decode_dt, 2),
            "decode_tok_per_s": round(decode_tokens / decode_dt, 1),
            "prefill_tok_per_s": round(prefill_n / prefill_dt, 1),
            "wave_avg_ms": round(decode_dt / n_waves * 1000.0, 2),
            "token_digest": digest,
            "kv_spec": str(eng.kv.k.sharding.spec) if mesh is not None else None,
        }
        if peak_tflops:
            peak = peak_tflops * 1e12 * tp  # the program owns tp chips
            row["mfu_prefill"] = round(prefill_flops / prefill_dt / peak, 4)
            row["mfu_decode"] = round(decode_flops / decode_dt / peak, 4)
        rows.append(row)
        del eng, params

    measured = [r for r in rows if "skipped" not in r]
    assert measured, "no tp point fit the device count"
    token_identity = len(set(digests)) == 1
    best = measured[-1]
    return {
        "metric": "tp_serving",
        "value": best["decisions_per_s"],
        "unit": f"decisions_per_s@tp{best['tp']}",
        "extra": {
            "model": "bench-tp",
            "weights": "random-init",
            "params_m": round(param_count(cfg) / 1e6, 1),
            "device_kind": device_kind,
            "host_device_mesh": host_mesh,
            "n_devices": n_dev,
            "slots": slots,
            "max_new_tokens": max_new,
            "waves": n_waves,
            "prefill_tokens": prefill_n,
            "token_identity": token_identity,
            "peak_bf16_tflops_per_chip": peak_tflops,
            "table": rows,
        },
    }


# ------------------------------------------------------- routed hybrid gate
def router_bench(args) -> dict:
    """`--preset router`: distill the two serving tiers and arena-gate
    the routed hybrid against BOTH arms alone (sched/router.py).

    The big arm is the learn-micro-class config distilled from the
    spread-lookahead teacher; the fast arm is a half-width student
    distilled from the SAME teacher (the production shape — same
    knowledge, less compute per decision). The hybrid routes per
    decision class (constraint complexity, deadline budget, snapshot
    warmth) and the preset FAILS unless it is no worse than EITHER arm
    alone on every gate axis AND the routing actually mixed — a gate
    where one arm never fires is an arm-vs-itself comparison, not a
    hybrid verdict. value is the hybrid's big-route fraction."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    from k8s_llm_scheduler_tpu.engine.local import build_local_backend
    from k8s_llm_scheduler_tpu.engine.tokenizer import build_builtin_tokenizer
    from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
    from k8s_llm_scheduler_tpu.rollout import GateConfig
    from k8s_llm_scheduler_tpu.sched.router import (
        RoutedBackend,
        RouterPolicy,
        distill_fast_checkpoint,
        run_hybrid_gate,
    )

    seed = args.seed if args.seed is not None else 0
    steps = int(getattr(args, "learn_steps", None) or 240)
    tokenizer_name = "numeric"
    big_base = LlamaConfig(
        name="router-big", vocab_size=512, d_model=64, n_layers=2,
        n_heads=2, n_kv_heads=1, d_ff=128, max_seq_len=4096,
        rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
    )
    fast_base = LlamaConfig(
        name="router-fast", vocab_size=512, d_model=32, n_layers=1,
        n_heads=2, n_kv_heads=1, d_ff=64, max_seq_len=4096,
        rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
    )
    _tok, big_cfg = build_builtin_tokenizer(tokenizer_name, big_base)
    _tok, fast_cfg = build_builtin_tokenizer(tokenizer_name, fast_base)
    work = Path(tempfile.mkdtemp(prefix="bench-router-"))

    def make_backend(cfg, ckpt):
        return build_local_backend(
            cfg=cfg, checkpoint_path=str(ckpt),
            tokenizer_name=tokenizer_name,
            temperature=0.0,  # the arena determinism contract
            max_slots=4, num_pages=128, page_size=64,
            max_pages_per_seq=32,
            prefill_buckets=(256, 512, 1024, 2048),
            chunk_steps=4,
        )

    try:
        t0 = time.perf_counter()
        big_ckpt = distill_fast_checkpoint(
            big_base, str(work / "big"), steps=steps, seed=seed,
            batch_size=8, seq_len=1536, lr=1e-3,
        )
        fast_ckpt = distill_fast_checkpoint(
            fast_base, str(work / "fast"), steps=steps, seed=seed + 1,
            batch_size=8, seq_len=1536, lr=1e-3,
        )
        distill_s = time.perf_counter() - t0

        # Arena snapshots are all cold and carry no deadline budget:
        # zero the cold surcharge so the route splits on constraint
        # complexity (selector pods -> big, uniform pods -> fast) —
        # the per-decision-class axis this gate is exercising.
        policy = RouterPolicy(big_cold_extra_ms=0.0, complexity_threshold=1)
        hybrids: list = []

        def make_hybrid():
            rb = RoutedBackend(
                make_backend(big_cfg, big_ckpt),
                make_backend(fast_cfg, fast_ckpt),
                policy,
            )
            hybrids.append(rb)
            return rb

        gate_cfg = GateConfig(
            seed=seed, nodes=8, pods=24, shapes=6, waves=2,
            spread_tolerance=0.05, wave_timeout_s=300.0,
        )
        t0 = time.perf_counter()
        verdict = run_hybrid_gate(
            lambda: make_backend(big_cfg, big_ckpt),
            lambda: make_backend(fast_cfg, fast_ckpt),
            make_hybrid,
            gate_cfg,
        )
        gate_s = time.perf_counter() - t0

        stats = dict(hybrids[0].stats_counters) if hybrids else {}
        routed = stats.get("routed_big", 0) + stats.get("routed_fast", 0)
        assert verdict["pass"], f"hybrid gate failed: {verdict['checks']}"
        assert stats.get("routed_big") and stats.get("routed_fast"), (
            f"routing did not mix (gate degenerates to arm-vs-itself): {stats}"
        )
        return {
            "metric": "router_gate",
            "value": round(stats["routed_big"] / routed, 3),
            "unit": "big_route_frac",
            "extra": {
                "seed": seed,
                "steps": steps,
                "gate_pass": verdict["pass"],
                "checks": verdict["checks"],
                "scores": verdict["scores"],
                "routing": stats,
                "big_params_m": round(param_count(big_cfg) / 1e6, 2),
                "fast_params_m": round(param_count(fast_cfg) / 1e6, 2),
                "distill_s": round(distill_s, 1),
                "gate_s": round(gate_s, 1),
                "model": "router-big/router-fast (teacher-distilled)",
            },
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)


# ------------------------------------------------------------ spec-vs-fused A/B
def spec_ab(
    model: str,
    draft: str = "tiny",
    spec_k: int = 4,
    max_new: int = 96,
    n_prompts: int = 4,
    reps: int = 2,
    params=None,
    arm: str = "draft",
    constrained: bool = True,
) -> dict:
    """Speculative-vs-FUSED-decode A/B on the general paged path.

    The baseline arm is the fused while_loop runtime (engine.decode_fused
    — plain generate() rides it since the async-spec round), NOT the
    chunked path: the spec arm must beat the fastest thing the engine
    already has, which is the ROADMAP item 3 bar. One engine, one set of
    weights; the arms alternate A/B/A/B in-process (same cross-run-weather
    rationale as tools/ab_decode.py). Greedy (temperature 0) and — by
    default — grammar-CONSTRAINED with a decision DFA, so both arms emit
    identical tokens through the serving configuration's masking
    machinery (dense transition table on both sides); the token-identity
    probe doubles as a correctness check on the real bench model.

    `arm`: "draft" (two-model async pipeline; `draft` names the config,
    or "self" for the acceptance-1.0 / overlap-1.0 upper bound) or
    "hidden" (draft-free hidden-transfer heads — random-init here; serve
    a train/hidden.py checkpoint for real acceptance).

    Beside tok/s the line reports the async pipeline's own books: the
    ROUND-OVERLAP fraction (rounds whose proposal block was
    device-resident before the round began), acceptance-weighted tok/s,
    per-request p50 latency, and the decode preset's round-trip extras —
    dispatch-gating sync boundaries per arm and the per-request cost
    they imply at the measured dispatch round trip.
    """
    import jax

    from k8s_llm_scheduler_tpu.engine.constrained import build_decision_dfa
    from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
    from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer
    from k8s_llm_scheduler_tpu.models.llama import init_params
    from k8s_llm_scheduler_tpu.observability.profiler import EngineProfiler
    from k8s_llm_scheduler_tpu.spec.decoder import SpeculativeDecoder
    from k8s_llm_scheduler_tpu.spec.draft import build_random_draft

    cfg = build_cfg(model)
    tok = ByteTokenizer(vocab_size=max(512, cfg.vocab_size))
    if params is None:
        params = init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(
        params, cfg, tok,
        num_pages=256, page_size=64, max_slots=2,
        max_pages_per_seq=-(-(256 + max_new + spec_k + 2) // 64),
        prefill_buckets=(128, 256, 512, 1024),
        chunk_steps=16, temperature=0.0,
    )
    profiler = EngineProfiler(cfg)
    eng.attach_profiler(profiler)
    if constrained:
        eng.set_grammar(build_decision_dfa(
            tok, [f"node-{chr(97 + i)}{i}" for i in range(8)],
            max_reason_tokens=max(max_new - 48, 16),
        ))
    if arm == "hidden":
        spec = SpeculativeDecoder(eng, arm="hidden", k=spec_k)
    elif draft == "self":
        spec = SpeculativeDecoder(eng, params, cfg, k=spec_k)
    else:
        # the SAME widening/init rule serving uses (spec/draft.py) — the
        # A/B must measure the configuration production would run
        draft_params, draft_cfg = build_random_draft(
            build_cfg(draft), tok.vocab_size, seed=1
        )
        spec = SpeculativeDecoder(eng, draft_params, draft_cfg, k=spec_k)
    eng.attach_spec(spec)

    if constrained:
        prompts = [
            tok.encode(f"Pick a node for pod-{40 + i}: ")
            for i in range(n_prompts)
        ]
    else:
        prompts = [
            tok.encode(_synthetic_text(40 + i, 200)) for i in range(n_prompts)
        ]
    # compile+warm both arms. Token identity is EXACT at f32 (pinned by
    # tests/test_spec.py + test_spec_async.py); at bf16 the two decode
    # implementations can flip a near-tie argmax (random-init top-2 logit
    # gaps are ~1e-2, bf16 KV rounding differs between the paged-block
    # and chunk-buffer paths), so the bench REPORTS the match instead of
    # asserting it.
    warm_spec = eng.generate(prompts[0], max_new, use_spec=True)
    warm_fused = eng.generate(prompts[0], max_new, use_spec=False)
    first_div = next(
        (
            i
            for i, (x, y) in enumerate(
                zip(warm_spec.token_ids, warm_fused.token_ids)
            )
            if x != y
        ),
        None,
    )

    # (time, ACTUAL tokens, gating sync boundaries, per-request
    # latencies) per rep: random-init greedy can hit EOS early, and the
    # two arms can stop at different lengths at bf16 — assuming
    # n_prompts*max_new would inflate both rates and skew the ratio.
    # Gating boundaries: on the spec arm EVERY sync gates the next
    # dispatch — the admission-state fetch, each round's verify fetch
    # (the ahead proposal is already in flight, the NEXT round's verify
    # is not), and any post-auto-disable step_fused drains (one chunk
    # per sync) — so the arm's total sync count IS its gated-boundary
    # count. A fused generate pays ONE gating boundary per request (all
    # chunks enqueue up front; the per-chunk harvests overlap later
    # chunks' device execution — the fused_ab argument).
    runs = {"fused": [], "spec": []}
    for _ in range(reps):
        for arm_name, use in (("fused", False), ("spec", True)):
            s0 = eng.stats["syncs"]
            lat = []
            t0 = time.perf_counter()
            n_toks = 0
            for p in prompts:
                t_req = time.perf_counter()
                n_toks += len(
                    eng.generate(p, max_new, use_spec=use).token_ids
                )
                lat.append((time.perf_counter() - t_req) * 1000.0)
            dt = time.perf_counter() - t0
            syncs = eng.stats["syncs"] - s0
            boundaries = syncs if arm_name == "spec" else len(prompts)
            runs[arm_name].append((dt, n_toks, syncs, boundaries, lat))
    tps = {
        a: round(max(n / dt for dt, n, _, _, _ in rs), 1)
        for a, rs in runs.items()
    }
    p50 = {
        a: round(
            statistics.median([ms for r in rs for ms in r[4]]), 2
        )
        for a, rs in runs.items()
    }
    syncs_per_req = {
        a: round(min(s for _, _, s, _, _ in rs) / n_prompts, 2)
        for a, rs in runs.items()
    }
    gating = {
        a: round(min(b for _, _, _, b, _ in rs) / n_prompts, 2)
        for a, rs in runs.items()
    }
    rtt = measure_dispatch_rtt_ms()
    snap = spec.stats.snapshot()
    psnap = profiler.snapshot().get("spec") or {}
    return {
        "metric": "spec_decode_ab",
        "value": round(tps["spec"] / tps["fused"], 3),
        "unit": "speedup_x",
        "extra": {
            "model": model,
            "weights": "random-init",
            "arm": arm,
            "draft": draft if arm == "draft" else None,
            "spec_k": spec_k,
            "max_new": max_new,
            "constrained": constrained,
            "baseline": "fused_decode",
            "decode_tok_per_s": tps,
            "raw_p50_ms": p50,
            "acceptance_rate": round(snap["acceptance_rate"], 4),
            "acceptance_weighted_tok_per_s": round(
                tps["spec"] * snap["acceptance_rate"], 1
            ),
            "tokens_per_round": round(snap["tokens_per_round"], 3),
            # the async pipeline's headline: fraction of rounds whose
            # proposal block was device-resident before the round began
            # (draft ran in the shadow of the previous verify sync)
            "round_overlap_fraction": round(snap["overlap_fraction"], 4),
            "spec_segment_frac": psnap.get("segment_frac"),
            "disables": snap["disables"],
            "fallback_requests": snap["fallback_requests"],
            # the decode preset's round-trip extras, per REQUEST: only
            # dispatch-gating sync boundaries pay a serialized dispatch
            # round trip (the ahead proposal and the fused chunk queue
            # are both already enqueued when their round's sync lands)
            "syncs_per_request": syncs_per_req,
            "gating_syncs_per_request": gating,
            # < 1 means the spec arm pays MORE gated round trips per
            # request than the fused baseline (one per round vs one per
            # request) — the round-trip tax the acceptance win must beat;
            # the overlap fraction above is what keeps the DRAFT's
            # latency off those gated paths entirely
            "rtt_boundary_reduction_x": round(
                gating["fused"] / max(gating["spec"], 1e-9), 2
            ),
            "dispatch_rtt_ms": rtt,
            "rtt_per_request_ms": {
                a: round(g * rtt, 1) for a, g in gating.items()
            },
            # None = greedy arms agreed token-for-token; an int is the
            # first bf16 near-tie flip (see comment at the warmup)
            "greedy_first_divergence": first_div,
            "note": (
                "random-init drafts/heads bound overhead (acceptance ~0 "
                "unless draft='self'); serve a distilled draft "
                "(train/distill.py) or trained hidden-transfer head "
                "(train/hidden.py) for real wins"
            ),
        },
    }


# --------------------------------------------------------- fused decode A/B
def fused_ab(
    model: str,
    quantize: str | None = None,
    max_new: int = 96,
    n_prompts: int = 4,
    reps: int = 2,
    params=None,
    peak_override: float | None = None,
) -> dict:
    """Fused-vs-chunked decode A/B on the general paged path.

    One engine, one set of weights, arms interleaved A/B/A/B in-process
    (the cross-run-weather rationale of tools/ab_decode.py). Greedy, so
    both arms SHOULD emit identical tokens — exact at f32 (pinned by
    tests/test_fused.py on the micro engine); at bf16 a near-tie argmax
    can flip, so the bench reports the first divergence instead of
    asserting. The headline figures: decode tok/s per arm, and HOST
    SYNCS PER REQUEST per arm — the fused runtime's claim is exactly
    that ratio (every gating sync pays one dispatch round trip).
    """
    import jax

    from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
    from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer
    from k8s_llm_scheduler_tpu.models.llama import init_params
    from k8s_llm_scheduler_tpu.observability.profiler import EngineProfiler

    cfg = build_cfg(model)
    tok = ByteTokenizer(vocab_size=max(512, cfg.vocab_size))
    peak_tflops, device_kind = detect_peak_tflops(peak_override)
    if params is None:
        if quantize == "int8":
            from k8s_llm_scheduler_tpu.models.quant import init_params_int8_host

            params = init_params_int8_host(0, cfg)
        else:
            params = init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(
        params, cfg, tok,
        num_pages=256, page_size=64, max_slots=max(n_prompts, 2),
        max_pages_per_seq=-(-(256 + max_new + 1) // 64) + 1,
        prefill_buckets=(128, 256, 512, 1024),
        chunk_steps=16, temperature=0.0,
    )
    profiler = EngineProfiler(cfg, peak_tflops=peak_override)
    eng.attach_profiler(profiler)
    eng.set_prefix(tok.encode(_synthetic_text(7, 400)))
    prompts = [
        tok.encode(_synthetic_text(60 + i, 200)) for i in range(n_prompts)
    ]

    def run_arm(fused: bool):
        ids = eng.add_requests(prompts, max_new_tokens=max_new)
        c0 = dict(eng.stats)
        t0 = time.perf_counter()
        out: dict[int, list[int]] = {}
        # DISPATCH-GATING sync boundaries: a chunked step() blocks on its
        # harvest before the next chunk can dispatch — every sync is a
        # full serialized round trip. decode_fused enqueues ALL chunks
        # back-to-back first, so only ONE boundary gates the pipeline
        # (the per-chunk harvests overlap later chunks' device
        # execution). This count, not the raw sync count, is what the
        # dispatch round trip multiplies.
        boundaries = 0
        if fused:
            boundaries += 1
            for fin in eng.decode_fused():
                out[fin.req_id] = fin.token_ids
        while len(out) < len(ids):
            boundaries += 1
            for fin in eng.step():
                out[fin.req_id] = fin.token_ids
        dt = time.perf_counter() - t0
        tokens = eng.stats["decode_tokens"] - c0["decode_tokens"]
        syncs = eng.stats["syncs"] - c0["syncs"]
        return [out[i] for i in ids], dt, tokens, syncs, boundaries

    # compile + warm both arms (and the identity probe)
    warm_chunked, *_ = run_arm(fused=False)
    warm_fused, *_ = run_arm(fused=True)
    first_div = None
    for row_c, row_f in zip(warm_chunked, warm_fused):
        div = next(
            (i for i, (a, b) in enumerate(zip(row_c, row_f)) if a != b),
            # equal prefix but different lengths (one arm hit EOS early)
            # IS a divergence — at the first position past the short row
            min(len(row_c), len(row_f))
            if len(row_c) != len(row_f)
            else None,
        )
        if div is not None:
            first_div = div if first_div is None else min(first_div, div)

    runs = {"chunked": [], "fused": []}
    for _ in range(reps):
        for arm, use_fused in (("chunked", False), ("fused", True)):
            _, dt, tokens, syncs, boundaries = run_arm(fused=use_fused)
            runs[arm].append((dt, tokens, syncs, boundaries))
    tps = {
        arm: round(max(n / dt for dt, n, _, _ in rs), 1)
        for arm, rs in runs.items()
    }
    syncs_per_req = {
        arm: round(min(s for _, _, s, _ in rs) / n_prompts, 2)
        for arm, rs in runs.items()
    }
    gating = {
        arm: min(b for _, _, _, b in rs) for arm, rs in runs.items()
    }
    ctx = eng.prefix_len + 200 + max_new / 2
    flops_per_tok = matmul_flops_per_token(cfg) + attn_flops_per_token(cfg, ctx)
    mfu = {}
    if peak_tflops:
        peak = peak_tflops * 1e12
        for arm, rs in runs.items():
            dt, tokens, _, _ = min(rs, key=lambda r: r[0] / max(r[1], 1))
            mfu[arm] = round(tokens * flops_per_tok / dt / peak, 4)
    snap = profiler.snapshot()
    out = {
        "metric": "fused_decode_ab",
        "value": round(tps["fused"] / tps["chunked"], 3),
        "unit": "speedup_x",
        "extra": {
            "model": model,
            "weights": "random-init",
            "quantize": quantize,
            "device_kind": device_kind,
            "max_new": max_new,
            "n_prompts": n_prompts,
            "decode_tok_per_s": tps,
            "syncs_per_request": syncs_per_req,
            # only DISPATCH-GATING sync boundaries pay a serialized
            # dispatch round trip (fused enqueues every chunk up front;
            # its per-chunk harvests overlap device execution), so this
            # ratio is the round-trip term's reduction on the paged
            # decode path
            "gating_syncs": gating,
            "rtt_boundary_reduction_x": round(
                gating["chunked"] / max(gating["fused"], 1), 2
            ),
            "fused_chunks": eng.stats["fused_chunks"],
            "fused_steps": eng.stats["fused_steps"],
            "fused_fallbacks": eng.stats["fused_fallbacks"],
            # None = greedy arms agreed token-for-token (exact at f32);
            # an int is the first bf16 near-tie flip position
            "greedy_first_divergence": first_div,
            "fused_profile": {
                k: v for k, v in (snap.get("fused") or {}).items()
                if k != "ring"
            },
        },
    }
    if mfu:
        out["extra"]["mfu_decode"] = mfu
        if mfu.get("chunked"):
            out["extra"]["mfu_decode_ratio"] = round(
                mfu["fused"] / mfu["chunked"], 3
            )
    del eng, params
    return out


async def decode_bench(args) -> dict:
    """`--preset decode`: the fused decode runtime end to end.

    Three books in one line, all as measured:
    - the fused-vs-chunked engine A/B (fused_ab): tok/s, MFU, and
      syncs-per-request both arms;
    - the scheduler-path decision p50 through the real stack
      (bench_preset), published as raw_p50_ms with the explicit
      meets_target_raw verdict — the <200ms bar is judged on THIS number;
    - dispatch_rtt_ms, the plain dispatch round trip on this machine.
    """
    ab = fused_ab(
        args.model,
        quantize=getattr(args, "quantize", None),
        n_prompts=min(args.slots, 8),
        peak_override=getattr(args, "peak_tflops", None),
    )
    sched = await bench_preset(args)
    rtt = measure_dispatch_rtt_ms()
    return {
        "metric": "decode_runtime",
        "value": ab["value"],
        "unit": "fused_speedup_x",
        "extra": {
            "model": args.model,
            "weights": "random-init",
            "preset": "decode",
            # decision latency through the scheduler stack, as measured
            "raw_p50_ms": sched["value"],
            "raw_decide_p50_ms": sched["extra"]["decide_p50_ms"],
            "raw_decide_p99_ms": sched["extra"]["decide_p99_ms"],
            "target_ms": TARGET_P50_MS,
            "meets_target_raw": bool(sched["value"] < TARGET_P50_MS),
            "dispatch_rtt_ms": rtt,
            # effective per-request round-trip cost on the paged decode
            # path: gating boundaries x one dispatch round trip, both arms
            "rtt_per_request_ms": {
                arm: round(g * rtt, 1)
                for arm, g in ab["extra"]["gating_syncs"].items()
            },
            "fused_ab": ab["extra"],
            "scheduler": sched["extra"],
        },
    }


# ----------------------------------------------------------------- suite/main
DEFAULTS = {
    # 16 slots: one 32-row wave measured WORSE than two pipelined 16-row
    # waves for burst1000 (wave compute dominates and pipelining both
    # overlaps the dispatch round trip and binds wave-1 followers early).
    # The default preset's 8 leaders ride the engine's half-width row
    # bucket, so its waves run at R=8.
    "pods": 64, "nodes": 32, "shapes": 8, "slots": 16, "model": "bench",
    "chunk_steps": 24, "max_new_tokens": 72, "temperature": 0.3,
    "rounds": 3, "perturb_idle": 0.0, "prefix_prewarm": 0.25,
}


def _preset_ns(
    preset: str,
    base: argparse.Namespace | None = None,
    **overrides,
) -> argparse.Namespace:
    ns = argparse.Namespace(**{**DEFAULTS, **PRESETS[preset], **overrides})
    ns.preset = preset
    ns.quantize = getattr(base, "quantize", None) if base else None
    ns.profile_dir = None
    return ns


def _emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


BASELINE_MODEL = "llama-3.2-1b-instruct"


def run_suite(args) -> None:
    async def suite():
        # default + burst1000 share the model/slots -> ONE backend, one set
        # of compiled programs (a rebuilt engine re-jits everything).
        ns_def = _preset_ns("default")
        ns_burst = _preset_ns("burst1000")
        def emit_partial(r: dict) -> None:
            # Emit every result as soon as it lands: if a driver timeout
            # kills the suite midway, the last complete line is still a
            # real metric. EVERY per-preset line is marked partial (on a
            # COPY — the suite object must not inherit the mark) so
            # metric-filtering consumers keep only the final headline.
            _emit({**r, "extra": {**r["extra"], "partial": True}})

        backend = build_backend(ns_def)
        try:
            r_def = await bench_preset(ns_def, backend)
            emit_partial(r_def)
            r_burst = await bench_preset(ns_burst, backend)
            emit_partial(r_burst)
            # steady-state arrivals, bounded to ONE round and run on the
            # SAME backend (identical engine geometry -> no re-jit), so
            # BENCH_r*.json tracks warm per-decision latency round over
            # round without inflating suite wall time.
            ns_steady = _preset_ns("steady")
            ns_steady.rounds = 1
            r_steady = await bench_preset(ns_steady, backend)
        finally:
            backend.close()
        emit_partial(r_steady)

        ns_long = _preset_ns("longctx")
        r_long = await bench_preset(ns_long)
        emit_partial(r_long)

        # BASELINE-model pass (VERDICT r03 #2): the recorded preset p50s
        # must exist at a REAL model size, not just the 18M bench model.
        # One shared 1B backend, default + burst1000, with the cold/warm
        # split reported per preset. 3 rounds each: a true median (the
        # measured rounds are seconds; the warmup compile dominates this
        # block's wall time either way). A failure here (OOM, compile
        # error) FAILS the suite: an 18M-toy headline standing in for a
        # 1B block that died is not a result.
        ns1_def = _preset_ns("default", model=BASELINE_MODEL, rounds=3)
        ns1_burst = _preset_ns("burst1000", model=BASELINE_MODEL, rounds=3)
        backend_1b = build_backend(ns1_def)
        try:
            r1_def = await bench_preset(ns1_def, backend_1b)
            emit_partial(r1_def)
            r1_burst = await bench_preset(ns1_burst, backend_1b)
            emit_partial(r1_burst)
        finally:
            backend_1b.close()
        return r_def, r_burst, r_long, r_steady, r1_def, r1_burst

    r_def, r_burst, r_long, r_steady, r1_def, r1_burst = asyncio.run(suite())

    tp_bench = model_throughput("bench", None, args.peak_tflops)
    _emit(tp_bench)
    tp_1b = model_throughput(BASELINE_MODEL, None, args.peak_tflops)
    _emit(tp_1b)
    # int8 weight-only path, bench-size: tracks the quantized decode/prefill
    # kernels every round (the 8B int8 run is a 20-30 min standalone:
    # `--preset throughput --model llama-3.1-8b-instruct --quantize int8`).
    tp_int8 = model_throughput("bench", "int8", args.peak_tflops)
    _emit(tp_int8)

    dispatch_rtt = measure_dispatch_rtt_ms()

    # The FULL suite object goes on its own (fat) line, second to last —
    # the driver's tail capture truncated r03's final line when everything
    # was folded into it and the round's headline was lost (VERDICT r03 #1).
    suite_line = {
        "metric": "suite_results",
        "value": r1_def["value"],
        "unit": "ms",
        "extra": {
            "presets": {
                "default": r_def["extra"],
                "burst1000": r_burst["extra"],
                "longctx": r_long["extra"],
                "steady": r_steady["extra"],
                "default@1b": r1_def["extra"],
                "burst1000@1b": r1_burst["extra"],
            },
            "throughput": {
                "bench": tp_bench["extra"],
                "llama-3.2-1b": tp_1b["extra"],
                "bench-int8": tp_int8["extra"],
            },
            "dispatch_rtt_ms": dispatch_rtt,
        },
    }
    _emit(suite_line)

    # LAST line: compact headline only — the BASELINE-model default-preset
    # p50 with its cold/warm split plus a one-level summary of the other
    # presets. Small enough that the driver's tail always parses it.
    def _mini(r):
        e = r["extra"]
        return {
            "p50_ms": r["value"],
            "p50_cold_ms": e.get("p50_cold_ms"),
            "p50_warm_ms": e.get("p50_warm_ms"),
        }

    top = r1_def
    headline = {
        "metric": "p50_decision_latency_ms",
        "value": top["value"],
        "unit": "ms",
        "vs_baseline": top["vs_baseline"],
        "extra": {
            "model": BASELINE_MODEL,
            "weights": "random-init",
            "preset": "default",
            "p50_cold_ms": top["extra"].get("p50_cold_ms"),
            "p50_warm_ms": top["extra"].get("p50_warm_ms"),
            "n_cold": top["extra"].get("n_cold"),
            "n_warm": top["extra"].get("n_warm"),
            "burst1000@1b": _mini(r1_burst),
            "default@bench": _mini(r_def),
            "burst1000@bench": _mini(r_burst),
            "target_ms": TARGET_P50_MS,
            "meets_target_raw": bool(top["value"] < TARGET_P50_MS),
            "longctx_p50_ms": r_long["value"],
            "steady_p99_ms": r_steady["extra"]["p99_ms"],
            "decisions_per_s_1b": tp_1b["extra"]["decisions_per_s"],
            "mfu_prefill_1b": tp_1b["extra"].get("mfu_prefill"),
            "dispatch_rtt_ms": dispatch_rtt,
            "baseline_note": "reference publishes no numbers; target p50<200ms (BASELINE.md)",
        },
    }
    _emit(headline)


def main() -> None:
    # Flag defaults are None sentinels so presets only fill flags the user
    # did NOT pass (an explicit `--pods 64` must survive `--preset burst1000`).
    parser = argparse.ArgumentParser()
    parser.add_argument("--pods", type=int, default=None)
    parser.add_argument("--nodes", type=int, default=None)
    parser.add_argument("--shapes", type=int, default=None)
    parser.add_argument("--slots", type=int, default=None)
    parser.add_argument("--model", default=None)
    parser.add_argument("--chunk-steps", type=int, default=None)
    parser.add_argument("--max-new-tokens", type=int, default=None)
    parser.add_argument("--temperature", type=float, default=None)
    parser.add_argument("--rounds", type=int, default=None)
    parser.add_argument(
        "--arrival-rate", type=float, default=None,
        help="pods/sec arrival pacing instead of burst-at-t0 (steady preset)",
    )
    parser.add_argument(
        "--perturb-idle", type=float, default=None,
        help="perturb node usage then idle this many seconds before each "
             "round's burst (restate preset: burst after a state change)",
    )
    parser.add_argument(
        "--prefix-prewarm", type=float, default=None,
        help="scheduler prefix-prewarm tick seconds (0 disables; the "
             "restate preset's A/B knob)",
    )
    parser.add_argument("--quantize", choices=["int8"], default=None)
    parser.add_argument(
        "--preset",
        choices=sorted(PRESETS) + ["suite", "throughput", "spec-ab"],
        default="suite",
    )
    parser.add_argument(
        "--spec-k", type=int, default=4,
        help="draft tokens per round for --preset spec-ab",
    )
    parser.add_argument(
        "--draft-model", default="tiny",
        help="draft config for --preset spec-ab ('self' = draft == target, "
             "the acceptance-1.0 / overlap-1.0 upper bound)",
    )
    parser.add_argument(
        "--spec-arm", choices=("draft", "hidden"), default="draft",
        help="--preset spec-ab arm: two-model async draft pipeline, or "
             "the draft-free hidden-transfer head (spec/hidden.py)",
    )
    parser.add_argument(
        "--spec-unconstrained", action="store_true",
        help="--preset spec-ab: drop the decision grammar (default is "
             "grammar-constrained greedy — the serving configuration)",
    )
    parser.add_argument(
        "--peak-tflops", type=float, default=None,
        help="chip peak dense bf16 TFLOP/s for MFU (auto-detected for known "
             "TPU device kinds)",
    )
    parser.add_argument(
        "--profile-dir", default=None,
        help="capture a jax.profiler device trace of the measured rounds "
             "(TensorBoard format) into this directory",
    )
    parser.add_argument(
        "--decode-matmul", choices=("dense", "ragged"), default=None,
        help="block-decode matmul impl for --preset throughput A/Bs "
             "(ops/ragged_matmul.py)",
    )
    parser.add_argument(
        "--seed", type=int, default=None,
        help="scenario seed for --preset arena (default 0)",
    )
    parser.add_argument(
        "--swaps", type=int, default=None,
        help="hot weight swaps performed under load for --preset rollout "
             "(default 6)",
    )
    parser.add_argument(
        "--learn-steps", type=int, default=None,
        help="finetune steps for --preset learn (default 300)",
    )
    parser.add_argument(
        "--trace", default=None,
        help="record the --preset arena trace here (replay with "
             "`cli sim --replay`)",
    )
    args = parser.parse_args()

    if args.preset == "suite":
        # The suite measures the FIXED BASELINE configurations; tuning flags
        # would silently not apply — demand an explicit preset for them.
        ignored = [
            name for name in (
                "pods", "nodes", "shapes", "slots", "model", "chunk_steps",
                "max_new_tokens", "temperature", "rounds", "arrival_rate",
                "quantize", "profile_dir", "decode_matmul", "perturb_idle",
                "prefix_prewarm", "seed", "trace", "swaps", "learn_steps",
            )
            if getattr(args, name) is not None
        ]
        if ignored:
            parser.error(
                f"--{'/--'.join(ignored)} have no effect on the default suite; "
                "pass an explicit --preset (or --preset throughput) with them"
            )
        run_suite(args)
        return
    if args.preset == "throughput":
        result = model_throughput(
            args.model or DEFAULTS["model"], args.quantize, args.peak_tflops,
            slots=args.slots or 16,
            decode_matmul=args.decode_matmul or "dense",
        )
        _emit(result)
        return
    if args.preset == "spec-ab":
        result = spec_ab(
            args.model or DEFAULTS["model"],
            draft=args.draft_model,
            spec_k=args.spec_k,
            arm=args.spec_arm,
            constrained=not args.spec_unconstrained,
        )
        _emit(result)
        return

    merged = {**DEFAULTS, **PRESETS[args.preset]}
    for key, value in merged.items():
        if getattr(args, key) is None:
            setattr(args, key, value)
    if args.rounds < 1:
        parser.error("--rounds must be >= 1")
    if args.preset == "arena":
        _emit(arena_bench(args))
        return
    if args.preset == "rollout":
        _emit(asyncio.run(rollout_bench(args)))
        return
    if args.preset == "obs-overhead":
        _emit(asyncio.run(obs_overhead_bench(args)))
        return
    if args.preset == "fleet":
        _emit(asyncio.run(fleet_bench(args)))
        return
    if args.preset == "autoscale":
        _emit(asyncio.run(autoscale_bench(args)))
        return
    if args.preset == "chaos":
        _emit(chaos_bench(args))
        return
    if args.preset == "recovery":
        _emit(recovery_bench(args))
        return
    if args.preset == "learn":
        _emit(learn_bench(args))
        return
    if args.preset == "burst":
        _emit(asyncio.run(burst_bench(args)))
        return
    if args.preset == "decode":
        _emit(asyncio.run(decode_bench(args)))
        return
    if args.preset == "tp-serving":
        _emit(tp_serving_bench(args))
        return
    if args.preset == "router":
        _emit(router_bench(args))
        return
    result = asyncio.run(bench_preset(args))
    result["extra"]["dispatch_rtt_ms"] = measure_dispatch_rtt_ms()
    _emit(result)


if __name__ == "__main__":
    main()
