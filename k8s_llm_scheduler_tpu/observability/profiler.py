"""Continuous engine profiler: per-wave step timelines + MFU loss terms.

Decode MFU at 1B measures 0.072 and the bench attributes 114 ms of p50 to
`dispatch_rtt_ms` — but until this module nothing in the repo could say
WHAT FRACTION of a decode wave's wall time is dispatch-boundary sync
versus host round-trip versus genuine matmul. That is the
synchronization-boundary accounting *Kernel Looping* (PAPERS.md) argues
dominates decode, and it is an attribution problem before it is an
optimization problem: ROADMAP items 1-2 (fused decode loop, dispatch-RTT
kill) need a measurement substrate that names the losses they exist to
remove.

This profiler fences every decision wave with perf_counter reads at each
jax.jit dispatch and block_until_ready boundary (engine/engine.py
submit_wave / harvest_wave; engine/local.py contributes the queue-side
fences) and buckets the wave's wall time into NAMED SEGMENTS that
telescope exactly:

    queue_stall    oldest item enqueued -> submit entered (admission wait,
                   coalescing window, group-switch fairness holds)
    dispatch       submit entered -> jit program enqueued + D2H started
                   (host-side tracing/enqueue cost — the dispatch boundary)
    dispatch_gap   dispatch done -> harvest entered (pipelining overlap:
                   the worker polls the queue / feeds later waves here)
    host_sync      harvest entered -> device_get returned (the
                   block_until_ready boundary: device tail + transfer +
                   host round trip)
    harvest        device_get returned -> results decoded on host
    unattributed   wall - sum(above): clock-fence residue, reported as its
                   own segment so coverage is verifiable (>= 95% of wave
                   wall by construction; the acceptance test pins it)

Overlapping those host segments, `device_compute` estimates when the
device was actually busy on this wave (dispatch end -> result ready; the
ready edge comes from the worker's is_ready() poll, or the device_get
return on a blocking harvest). From token counts and the model config the
profiler computes per-wave achieved FLOPs, so `mfu_decode` decomposes:

    mfu_decode + sum(mfu_loss[segment]) ~= mfu_device

where mfu_device is what the device-busy time alone would achieve and
each loss term charges a named idle segment its share of the gap. The
bounded ring exports at /debug/profile (observability/metrics.py) and the
windowed means surface as Prometheus gauges — this is the layer every
subsequent perf PR proves itself against.

The admission plane (engine/admission/) gets the same treatment: each
packed admission contributes a record whose PACK_SEGMENTS telescope to
its host wall (admission_pack / chunk_prefill / decode_piggyback /
unattributed, sum == wall), and a `prefill_tokens_per_decision` gauge —
windowed (wave suffix + packed + prefix tokens ACTUALLY prefilled) per
decision — measures the delta-encoding claim directly: prefill cost
scaling with what changed, not cluster size. The speculative pipeline
(spec/decoder.py) books per-request SPEC_SEGMENTS (draft / verify /
rollback / unattributed, sum == wall) plus the measured round-overlap
fraction — the draft-runs-in-the-shadow-of-the-verify claim, measured.

Cost discipline: all fencing is perf_counter reads on the PER-WAVE path
(waves run at ~10-60/s, never per token); with no profiler attached the
engine pays one None check per wave. bench.py --preset obs-overhead
re-measures the budget with the profiler on.
"""

from __future__ import annotations

import logging
import statistics
import threading
import time
from collections import deque
from typing import Any

logger = logging.getLogger(__name__)

# Telescoping host-side segments, in timeline order. `device_compute` is
# NOT in this list: it overlaps dispatch_gap/host_sync and is reported as
# its own (estimated) figure beside them.
SEGMENTS = (
    "queue_stall",
    "dispatch",
    "dispatch_gap",
    "host_sync",
    "harvest",
    "unattributed",
)

# Packed-admission segments (engine.admit_packed — the admission plane),
# telescoping over each pack's host wall time with the same sum==wall
# identity the wave segments keep: admission_pack is host-side packing /
# bookkeeping, chunk_prefill the packed block-diagonal prefill dispatches,
# decode_piggyback the SARATHI decode chunks interleaved between them.
PACK_SEGMENTS = (
    "admission_pack",
    "chunk_prefill",
    "decode_piggyback",
    "unattributed",
)

# Fused-decode segments (engine.step_fused / decode_fused — the fused
# on-device runtime, engine/fused/): telescoping over each fused harvest's
# host wall with the same sum==wall identity. dispatch is the back-to-back
# chunk enqueues (no syncs), host_sync the per-chunk device_get window,
# harvest the host-side token decode after the last sync.
FUSED_SEGMENTS = (
    "dispatch",
    "host_sync",
    "harvest",
    "unattributed",
)

# Speculative-decoding segments (spec/decoder.py — the async
# propose/verify pipeline): telescoping over each spec REQUEST's host
# wall with the same sum==wall identity. draft covers propose dispatches
# (draft prefill + fresh + ahead — ~0 for the hidden arm, whose
# proposals ride inside the verify program), verify the verify dispatch
# plus the round's single device_get, rollback the paged-KV truncate +
# host emit bookkeeping. Beside the segments, the books carry the round
# OVERLAP fraction — rounds whose proposal block was device-resident
# before the round began, i.e. the draft work hidden behind the previous
# verify sync — the async pipeline's headline.
SPEC_SEGMENTS = (
    "draft",
    "verify",
    "rollback",
    "unattributed",
)

# Peak dense bf16 TFLOP/s by jax device_kind (public spec sheets). Shared
# with bench.py's MFU figures so the profiler's decomposition and the
# bench headline always normalize against the same peak.
PEAK_BF16_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


def matmul_flops_per_token(cfg) -> float:
    """Matmul FLOPs for one token's forward pass (2*MACs), as the model's
    config counts them (models/configs.py: dense weights for the Llama
    family, ACTIVE parameters — router, selected and shared experts — for
    an expert layer; for MlaScmoeConfig's expert-parallel share, of a
    token's picks those that fall on experts held here under even routing,
    identity experts at nothing). Formerly bench.py's accounting — here so the
    profiler's MFU decomposition and the bench headline share one set of
    books."""
    return cfg.matmul_flops_per_token()


def attn_flops_per_token(cfg, ctx: float) -> float:
    """Attention score+value FLOPs for one token attending to `ctx` keys
    (a config whose layers see a window of them counts it itself:
    Cohere2MoeConfig.attn_flops_per_token)."""
    if hasattr(cfg, "attn_flops_per_token"):
        return cfg.attn_flops_per_token(ctx)
    return cfg.attn_flops_per_key() * ctx


def detect_peak_tflops(override: float | None = None) -> tuple[float | None, str]:
    """(peak bf16 TFLOP/s or None if unknown, device kind)."""
    try:
        import jax

        kind = jax.devices()[0].device_kind
    except Exception:  # pragma: no cover - no backend at all
        kind = "unknown"
    if override is not None:
        return override, kind
    return PEAK_BF16_TFLOPS.get(kind), kind


def measure_dispatch_rtt_ms(samples: int = 5) -> float:
    """Median host time, in ms, of dispatching a trivial jitted program
    (x + 1 on 8 floats, already compiled) and `device_get`-ing its
    result: one dispatch plus one blocking fetch on this machine."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.float32)
    jax.device_get(f(x))  # compile + warm
    out = []
    for _ in range(samples):
        t0 = time.perf_counter()
        jax.device_get(f(x))  # graftlint: ok[device-sync-in-loop] — the round trip IS what is timed
        out.append((time.perf_counter() - t0) * 1000.0)
    return round(statistics.median(out), 3)


class EngineProfiler:
    """Per-wave step-timeline recorder for one InferenceEngine.

    The engine owns the fences (it is the only code that knows where its
    dispatch and sync boundaries are); this class owns the bookkeeping:
    in-flight wave state keyed by handle identity, a bounded ring of
    completed wave records, and the derived segment/MFU aggregates.

    Thread model: on_submit/note_ready/on_harvest run on the engine-owner
    thread; note_admission runs there too (engine/local._submit_waves).
    snapshot()/gauges() are called from metrics-server handler threads —
    ring and totals are guarded by one lock, acquired once per wave and
    once per scrape.
    """

    def __init__(
        self,
        cfg: Any = None,
        *,
        window: int = 256,
        peak_tflops: float | None = None,
        clock=time.perf_counter,
    ) -> None:
        self.cfg = cfg
        self.window = max(1, int(window))
        self._clock = clock
        peak, kind = detect_peak_tflops(peak_tflops)
        self.peak_flops = peak * 1e12 if peak else None
        self.device_kind = kind
        self._lock = threading.Lock()
        self._ring: deque[dict] = deque(maxlen=self.window)
        # in-flight fence state, keyed by id(handle): a handle is submitted
        # and harvested exactly once, and the engine-owner thread does both
        self._open: dict[int, dict] = {}
        self._wave_counter = 0
        self._totals = {name: 0.0 for name in SEGMENTS}
        self._totals["device_compute"] = 0.0
        self._totals["wall"] = 0.0
        self._flops_total = 0.0
        self._tokens_total = 0
        self.waves_profiled = 0
        # CUMULATIVE (never-windowed) segment books beside the windowed
        # ones: monotone counters the SLO burn-rate engine can window
        # itself (delta against its own baselines) — an error_rate
        # objective over queue_stall_ms_total/wall_ms_total is the
        # admission-pressure objective the autoscaler consumes without a
        # custom stats provider. The windowed `*_frac` gauges cannot
        # serve that role: eviction makes them non-monotone.
        self._cum = {name: 0.0 for name in SEGMENTS}
        self._cum["wall"] = 0.0
        # Admission-plane books: per-pack records (engine.admit_packed)
        # and the prefill-tokens-per-decision gauge inputs. Prefix
        # prefills contribute only their NON-REUSED tokens — the delta
        # path's O(changed) claim is measured on exactly this figure.
        self._pack_ring: deque[dict] = deque(maxlen=self.window)
        self._pack_totals = {name: 0.0 for name in PACK_SEGMENTS}
        self._pack_totals["wall"] = 0.0
        # Fused-decode books (engine/fused/): per-harvest records with
        # telescoping FUSED_SEGMENTS and their own MFU figure — the
        # before/after proof the fused runtime is measured against.
        self._fused_ring: deque[dict] = deque(maxlen=self.window)
        self._fused_totals = {name: 0.0 for name in FUSED_SEGMENTS}
        self._fused_totals["wall"] = 0.0
        self._fused_flops = 0.0
        self._fused_tokens = 0
        self.fused_profiled = 0
        self._prefix_prefills: deque[tuple[int, int]] = deque(
            maxlen=self.window
        )  # (tokens prefilled, prefix length)
        self.packs_profiled = 0
        # Speculative-pipeline books (spec/decoder.py): per-request
        # records with telescoping SPEC_SEGMENTS plus windowed round /
        # overlap counts — the draft/verify overlap fraction is derived
        # from exactly these.
        self._spec_ring: deque[dict] = deque(maxlen=self.window)
        self._spec_totals = {name: 0.0 for name in SPEC_SEGMENTS}
        self._spec_totals["wall"] = 0.0
        self._spec_rounds = 0
        self._spec_overlapped = 0
        self._spec_tokens = 0
        self.spec_profiled = 0
        # Decision-flow books: (XLA dispatches, decisions completed)
        # deltas booked at each completion window (engine.
        # _book_decision_flow). Dispatch deltas telescope exactly — every
        # stats "dispatches" bump lands in exactly one window — so the
        # lifetime sum of d_dispatches equals the engine's dispatch counter.
        self._flow_ring: deque[tuple[int, int]] = deque(maxlen=self.window)
        self._flow_dispatches = 0
        self._flow_decisions = 0
        self.closed = False

    # ------------------------------------------------------------- fences
    def on_submit(
        self,
        handle: Any,
        t_enter: float,
        t_exit: float,
        *,
        suffix_tokens: int,
        n_requests: int,
        prefix_len: int,
        cold_compile: bool,
    ) -> None:
        """submit_wave fencing: t_enter/t_exit bracket the jit dispatch
        (prompt packing + program enqueue + D2H kick)."""
        with self._lock:
            if len(self._open) > 64:
                # a leaked handle (harvest raised before reaching the
                # profiler fence) must not grow this map forever
                self._open.clear()
            self._open[id(handle)] = {
                "submit_enter": t_enter,
                "submit_exit": t_exit,
                "enqueued_at": None,
                "ready_at": None,
                "suffix_tokens": int(suffix_tokens),
                "n_requests": int(n_requests),
                "prefix_len": int(prefix_len),
                "cold_compile": bool(cold_compile),
            }

    def note_admission(self, handle: Any, oldest_enqueued_at: float) -> None:
        """Queue-side fence from engine/local.py: the oldest batch item's
        enqueue time (perf_counter) — the wave's queue_stall anchor."""
        with self._lock:
            st = self._open.get(id(handle))
            if st is not None:
                st["enqueued_at"] = float(oldest_enqueued_at)

    def note_ready(self, handle: Any) -> None:
        """The worker's is_ready() poll observed the device result landing
        (first call wins); on a blocking harvest the device_get return
        stands in for this edge."""
        now = self._clock()
        with self._lock:
            st = self._open.get(id(handle))
            if st is not None and st["ready_at"] is None:
                st["ready_at"] = now

    def on_harvest(
        self,
        handle: Any,
        t_enter: float,
        t_sync: float,
        t_exit: float,
        *,
        decode_tokens: int,
        model_calls: int,
        ready_at_entry: bool,
    ) -> None:
        """harvest_wave fencing: t_enter -> t_sync brackets the device_get
        (the block_until_ready boundary), t_sync -> t_exit the host-side
        token decode. Completes the wave record."""
        with self._lock:
            st = self._open.pop(id(handle), None)
        if st is None:
            return  # submitted before the profiler attached
        ready_at = st["ready_at"]
        if ready_at is None:
            # never observed by a poll: the result landed either before
            # harvest entry (charge the gap) or at the device_get return
            ready_at = t_enter if ready_at_entry else t_sync
        start = st["enqueued_at"]
        if start is None or start > st["submit_enter"]:
            start = st["submit_enter"]
        seg = {
            "queue_stall": max(st["submit_enter"] - start, 0.0),
            "dispatch": max(st["submit_exit"] - st["submit_enter"], 0.0),
            "dispatch_gap": max(t_enter - st["submit_exit"], 0.0),
            "host_sync": max(t_sync - t_enter, 0.0),
            "harvest": max(t_exit - t_sync, 0.0),
        }
        wall = max(t_exit - start, 0.0)
        seg["unattributed"] = max(wall - sum(seg.values()), 0.0)
        device = min(max(ready_at - st["submit_exit"], 0.0), wall)
        suffix_tokens = st["suffix_tokens"]
        tokens = suffix_tokens + int(decode_tokens)
        flops = self._wave_flops(
            st["prefix_len"], suffix_tokens, int(decode_tokens),
            st["n_requests"],
        )
        record = {
            "wave": 0,  # stamped under the lock below
            "n_requests": st["n_requests"],
            "cold_compile": st["cold_compile"],
            "wall_ms": wall * 1000.0,
            "segments_ms": {k: v * 1000.0 for k, v in seg.items()},
            "device_compute_ms": device * 1000.0,
            "suffix_tokens": suffix_tokens,
            "decode_tokens": int(decode_tokens),
            "model_calls": int(model_calls),
            "flops": flops,
        }
        with self._lock:
            self._wave_counter += 1
            record["wave"] = self._wave_counter
            # the aggregates are WINDOWED over the ring: an evicted wave's
            # contribution leaves the books, so segment_frac / mfu gauges
            # track the last `window` waves and a fresh regression moves
            # them immediately instead of drowning in lifetime history
            if len(self._ring) == self._ring.maxlen:
                old = self._ring[0]
                if not old["cold_compile"]:
                    for name in SEGMENTS:
                        self._totals[name] = max(
                            self._totals[name]
                            - old["segments_ms"].get(name, 0.0) / 1000.0,
                            0.0,
                        )
                    self._totals["device_compute"] = max(
                        self._totals["device_compute"]
                        - old["device_compute_ms"] / 1000.0,
                        0.0,
                    )
                    self._totals["wall"] = max(
                        self._totals["wall"] - old["wall_ms"] / 1000.0, 0.0
                    )
                    self._flops_total = max(
                        self._flops_total - old["flops"], 0.0
                    )
                    self._tokens_total = max(
                        self._tokens_total
                        - (old["suffix_tokens"] + old["decode_tokens"]),
                        0,
                    )
            self._ring.append(record)
            self.waves_profiled += 1
            # cold-compile waves hit the ring (they are real wall time the
            # operator should see) but stay out of the MFU aggregates —
            # jit time would poison the loss attribution exactly the way
            # it poisons the service-time EMA (engine/local.py)
            if not st["cold_compile"]:
                for name in SEGMENTS:
                    self._totals[name] += seg.get(name, 0.0)
                    self._cum[name] += seg.get(name, 0.0)
                self._totals["device_compute"] += device
                self._totals["wall"] += wall
                self._cum["wall"] += wall
                self._flops_total += flops
                self._tokens_total += tokens

    def note_prefix_prefill(self, tokens_prefilled: int, prefix_len: int) -> None:
        """A cluster-state prefix (re)prefill happened: `tokens_prefilled`
        is what was actually COMPUTED (0 on a cache hit; only the
        non-reused tail on an LCP-seeded / pinned-snapshot prefill), so
        the prefill-tokens-per-decision gauge credits delta encoding with
        exactly the work it skipped."""
        with self._lock:
            self._prefix_prefills.append(
                (int(tokens_prefilled), int(prefix_len))
            )

    def on_pack(
        self,
        *,
        wall_s: float,
        chunk_prefill_s: float,
        piggyback_s: float,
        n_prompts: int,
        tokens: int,
        chunks: int,
    ) -> None:
        """One packed admission completed dispatching (engine.admit_packed;
        the host never synced — segments are host-side enqueue walls).
        admission_pack = wall minus the measured dispatch segments (the
        packing/bookkeeping share); the identity sum(segments) == wall
        holds by construction and the acceptance test pins it."""
        wall = max(float(wall_s), 0.0)
        seg = {
            "chunk_prefill": max(float(chunk_prefill_s), 0.0),
            "decode_piggyback": max(float(piggyback_s), 0.0),
        }
        seg["admission_pack"] = max(
            wall - seg["chunk_prefill"] - seg["decode_piggyback"], 0.0
        )
        seg["unattributed"] = max(wall - sum(seg.values()), 0.0)
        record = {
            "pack": 0,  # stamped under the lock below
            "n_prompts": int(n_prompts),
            "tokens": int(tokens),
            "chunks": int(chunks),
            "wall_ms": wall * 1000.0,
            "segments_ms": {k: v * 1000.0 for k, v in seg.items()},
        }
        with self._lock:
            self.packs_profiled += 1
            record["pack"] = self.packs_profiled
            if len(self._pack_ring) == self._pack_ring.maxlen:
                old = self._pack_ring[0]
                for name in PACK_SEGMENTS:
                    self._pack_totals[name] = max(
                        self._pack_totals[name]
                        - old["segments_ms"].get(name, 0.0) / 1000.0,
                        0.0,
                    )
                self._pack_totals["wall"] = max(
                    self._pack_totals["wall"] - old["wall_ms"] / 1000.0, 0.0
                )
            self._pack_ring.append(record)
            for name in PACK_SEGMENTS:
                self._pack_totals[name] += seg[name]
            self._pack_totals["wall"] += wall

    def on_fused(
        self,
        *,
        wall_s: float,
        dispatch_s: float,
        sync_s: float,
        harvest_s: float,
        steps: int,
        tokens: int,
        chunks: int,
        ctx: float = 0.0,
    ) -> None:
        """One fused harvest completed (engine.step_fused / decode_fused).
        The three measured segments partition the wall by construction
        (consecutive perf_counter fences), so sum(segments) == wall holds
        exactly and the acceptance test pins it. `tokens` counts EMITTED
        tokens (pad-filtered, early-exit aware) — never chunk capacity —
        and `ctx` is the mean decode attention context for the FLOP books.
        """
        wall = max(float(wall_s), 0.0)
        seg = {
            "dispatch": max(float(dispatch_s), 0.0),
            "host_sync": max(float(sync_s), 0.0),
            "harvest": max(float(harvest_s), 0.0),
        }
        seg["unattributed"] = max(wall - sum(seg.values()), 0.0)
        flops = 0.0
        if self.cfg is not None and tokens > 0:
            flops = tokens * (
                matmul_flops_per_token(self.cfg)
                + attn_flops_per_token(self.cfg, max(float(ctx), 0.0))
            )
        record = {
            "harvest": 0,  # stamped under the lock below
            "chunks": int(chunks),
            "steps": int(steps),
            "tokens": int(tokens),
            "wall_ms": wall * 1000.0,
            "segments_ms": {k: v * 1000.0 for k, v in seg.items()},
            "flops": flops,
        }
        with self._lock:
            self.fused_profiled += 1
            record["harvest"] = self.fused_profiled
            if len(self._fused_ring) == self._fused_ring.maxlen:
                old = self._fused_ring[0]
                for name in FUSED_SEGMENTS:
                    self._fused_totals[name] = max(
                        self._fused_totals[name]
                        - old["segments_ms"].get(name, 0.0) / 1000.0,
                        0.0,
                    )
                self._fused_totals["wall"] = max(
                    self._fused_totals["wall"] - old["wall_ms"] / 1000.0, 0.0
                )
                self._fused_flops = max(self._fused_flops - old["flops"], 0.0)
                self._fused_tokens = max(
                    self._fused_tokens - old["tokens"], 0
                )
            self._fused_ring.append(record)
            for name in FUSED_SEGMENTS:
                self._fused_totals[name] += seg.get(name, 0.0)
            self._fused_totals["wall"] += wall
            self._fused_flops += flops
            self._fused_tokens += int(tokens)

    def on_spec(
        self,
        *,
        wall_s: float,
        draft_s: float,
        verify_s: float,
        rollback_s: float,
        rounds: int,
        overlapped_rounds: int,
        tokens: int,
        arm: str = "draft",
        disabled: bool = False,
    ) -> None:
        """One speculative request closed (spec/decoder.py — at
        completion, or at the auto-disable hand-off, in which case the
        record covers only the speculative phase). The three measured
        segments partition the wall by construction (consecutive
        perf_counter fences accumulated over the request's rounds), so
        sum(SPEC_SEGMENTS) == wall holds exactly and the acceptance test
        pins it. `overlapped_rounds` counts rounds whose proposal block
        was device-resident when the round began — the draft stream
        running in the shadow of the verify."""
        wall = max(float(wall_s), 0.0)
        seg = {
            "draft": max(float(draft_s), 0.0),
            "verify": max(float(verify_s), 0.0),
            "rollback": max(float(rollback_s), 0.0),
        }
        seg["unattributed"] = max(wall - sum(seg.values()), 0.0)
        record = {
            "request": 0,  # stamped under the lock below
            "arm": str(arm),
            "rounds": int(rounds),
            "overlapped_rounds": int(overlapped_rounds),
            "tokens": int(tokens),
            "disabled": bool(disabled),
            "wall_ms": wall * 1000.0,
            "segments_ms": {k: v * 1000.0 for k, v in seg.items()},
        }
        with self._lock:
            self.spec_profiled += 1
            record["request"] = self.spec_profiled
            if len(self._spec_ring) == self._spec_ring.maxlen:
                old = self._spec_ring[0]
                for name in SPEC_SEGMENTS:
                    self._spec_totals[name] = max(
                        self._spec_totals[name]
                        - old["segments_ms"].get(name, 0.0) / 1000.0,
                        0.0,
                    )
                self._spec_totals["wall"] = max(
                    self._spec_totals["wall"] - old["wall_ms"] / 1000.0, 0.0
                )
                self._spec_rounds = max(self._spec_rounds - old["rounds"], 0)
                self._spec_overlapped = max(
                    self._spec_overlapped - old["overlapped_rounds"], 0
                )
                self._spec_tokens = max(self._spec_tokens - old["tokens"], 0)
            self._spec_ring.append(record)
            for name in SPEC_SEGMENTS:
                self._spec_totals[name] += seg.get(name, 0.0)
            self._spec_totals["wall"] += wall
            self._spec_rounds += int(rounds)
            self._spec_overlapped += int(overlapped_rounds)
            self._spec_tokens += int(tokens)

    def on_decision_flow(self, d_dispatches: int, d_decisions: int) -> None:
        """Book one completion window's (dispatch delta, decision delta).
        The engine calls this whenever decisions complete, with the XLA
        dispatches issued since the PREVIOUS completion window — deltas
        telescope, so the windowed ratio charges every dispatch to
        exactly one batch of decisions."""
        d_disp = max(int(d_dispatches), 0)
        d_done = max(int(d_decisions), 0)
        if d_done <= 0:
            return
        with self._lock:
            if len(self._flow_ring) == self._flow_ring.maxlen:
                old_disp, old_done = self._flow_ring[0]
                self._flow_dispatches = max(
                    self._flow_dispatches - old_disp, 0
                )
                self._flow_decisions = max(
                    self._flow_decisions - old_done, 0
                )
            self._flow_ring.append((d_disp, d_done))
            self._flow_dispatches += d_disp
            self._flow_decisions += d_done

    def dispatches_per_decision(self) -> float | None:
        """Windowed XLA dispatches per completed decision. None until a
        completion window has been booked."""
        with self._lock:
            if self._flow_decisions <= 0:
                return None
            return round(self._flow_dispatches / self._flow_decisions, 4)

    def _prefill_tokens_per_decision_locked(self) -> float | None:
        """Windowed prefill tokens per decision: (wave suffix tokens +
        packed tokens + prefix tokens actually prefilled) / decisions.
        Caller holds the lock."""
        decisions = sum(r["n_requests"] for r in self._ring) + sum(
            r["n_prompts"] for r in self._pack_ring
        )
        if decisions <= 0:
            return None
        tokens = (
            sum(r["suffix_tokens"] for r in self._ring)
            + sum(r["tokens"] for r in self._pack_ring)
            + sum(t for t, _ in self._prefix_prefills)
        )
        return tokens / decisions

    # -------------------------------------------------------------- flops
    def _wave_flops(
        self,
        prefix_len: int,
        suffix_tokens: int,
        decode_tokens: int,
        n_requests: int = 1,
    ) -> float:
        """Achieved FLOPs of one wave: suffix prefill + block decode, both
        attending to the shared prefix (mean PER-REQUEST context ~ prefix +
        half that request's suffix+emission — same estimator bench.py's
        MFU uses; the wave total must be apportioned or a batched wave's
        attention term is overstated n_requests-fold)."""
        if self.cfg is None:
            return 0.0
        n = suffix_tokens + decode_tokens
        if n <= 0:
            return 0.0
        per_req = n / max(int(n_requests), 1)
        ctx = prefix_len + (per_req / 2.0)
        return n * (
            matmul_flops_per_token(self.cfg)
            + attn_flops_per_token(self.cfg, ctx)
        )

    # ------------------------------------------------------------- exports
    def _mfu(
        self, flops: float, wall: float, device: float, totals: dict
    ) -> dict | None:
        """The decomposition: mfu_decode + sum(loss terms) ~= mfu_device.

        The device is busy during [dispatch end, ready], which overlaps
        dispatch_gap and host_sync; each segment's loss term charges its
        NON-OVERLAPPED (device-idle) share, so the identity holds by
        construction: loss[s] = mfu_device * idle_s / wall and
        sum(idle) + device = wall. `totals` is the caller's copy taken
        under ONE lock acquisition together with flops/wall/device — a
        re-read here could include a wave the other figures don't."""
        if not self.peak_flops or wall <= 0 or flops <= 0:
            return None
        mfu = flops / wall / self.peak_flops
        if device <= 0:
            return {"decode": round(mfu, 5)}
        mfu_device = flops / device / self.peak_flops
        seg = {name: totals[name] for name in SEGMENTS}
        # device busy overlaps the gap first, then the sync window
        overlap_gap = min(seg["dispatch_gap"], device)
        overlap_sync = min(seg["host_sync"], device - overlap_gap)
        idle = dict(seg)
        idle["dispatch_gap"] = max(seg["dispatch_gap"] - overlap_gap, 0.0)
        idle["host_sync"] = max(seg["host_sync"] - overlap_sync, 0.0)
        loss = {
            name: round(mfu_device * idle_s / wall, 5)
            for name, idle_s in idle.items()
            if idle_s > 0
        }
        return {
            "decode": round(mfu, 5),
            "device": round(mfu_device, 5),
            "busy_frac": round(device / wall, 4),
            "loss": loss,
        }

    def snapshot(self) -> dict:
        """The /debug/profile payload: windowed segment totals/means, the
        MFU decomposition, and the per-wave ring."""
        with self._lock:
            ring = list(self._ring)
            totals = dict(self._totals)
            flops = self._flops_total
            tokens = self._tokens_total
            waves = self.waves_profiled
            pack_ring = list(self._pack_ring)
            pack_totals = dict(self._pack_totals)
            packs = self.packs_profiled
            fused_ring = list(self._fused_ring)
            fused_totals = dict(self._fused_totals)
            fused_flops = self._fused_flops
            fused_tokens = self._fused_tokens
            fused = self.fused_profiled
            spec_ring = list(self._spec_ring)
            spec_totals = dict(self._spec_totals)
            spec_rounds = self._spec_rounds
            spec_overlapped = self._spec_overlapped
            spec_tokens = self._spec_tokens
            spec = self.spec_profiled
            flow_disp = self._flow_dispatches
            flow_done = self._flow_decisions
            tpd = self._prefill_tokens_per_decision_locked()
        wall = totals["wall"]
        n_warm = sum(1 for r in ring if not r["cold_compile"])
        out: dict[str, Any] = {
            "waves_profiled": waves,
            "window": self.window,
            "device_kind": self.device_kind,
            "peak_bf16_tflops": (
                self.peak_flops / 1e12 if self.peak_flops else None
            ),
            "wall_ms_total": round(wall * 1000.0, 3),
            "segments_ms_total": {
                name: round(totals[name] * 1000.0, 3) for name in SEGMENTS
            },
            "device_compute_ms_total": round(
                totals["device_compute"] * 1000.0, 3
            ),
            "segment_frac": {
                name: round(totals[name] / wall, 4) if wall > 0 else 0.0
                for name in SEGMENTS
            },
            "coverage_frac": (
                round(
                    sum(totals[n] for n in SEGMENTS if n != "unattributed")
                    / wall,
                    4,
                )
                if wall > 0
                else 0.0
            ),
            "tokens": tokens,
            "achieved_tflops": (
                round(flops / wall / 1e12, 4) if wall > 0 else 0.0
            ),
            "warm_waves_in_window": n_warm,
            "ring": ring,
        }
        mfu = self._mfu(flops, wall, totals["device_compute"], totals)
        if mfu is not None:
            out["mfu"] = mfu
        if packs:
            pack_wall = pack_totals["wall"]
            out["packs"] = {
                "packs_profiled": packs,
                "wall_ms_total": round(pack_wall * 1000.0, 3),
                "segments_ms_total": {
                    name: round(pack_totals[name] * 1000.0, 3)
                    for name in PACK_SEGMENTS
                },
                "segment_frac": {
                    name: (
                        round(pack_totals[name] / pack_wall, 4)
                        if pack_wall > 0
                        else 0.0
                    )
                    for name in PACK_SEGMENTS
                },
                "ring": pack_ring,
            }
        if fused:
            fused_wall = fused_totals["wall"]
            fused_out: dict[str, Any] = {
                "harvests_profiled": fused,
                "tokens": fused_tokens,
                "wall_ms_total": round(fused_wall * 1000.0, 3),
                "segments_ms_total": {
                    name: round(fused_totals[name] * 1000.0, 3)
                    for name in FUSED_SEGMENTS
                },
                "segment_frac": {
                    name: (
                        round(fused_totals[name] / fused_wall, 4)
                        if fused_wall > 0
                        else 0.0
                    )
                    for name in FUSED_SEGMENTS
                },
                "ring": fused_ring,
            }
            if fused_wall > 0:
                fused_out["tokens_per_s"] = round(
                    fused_tokens / fused_wall, 1
                )
                fused_out["achieved_tflops"] = round(
                    fused_flops / fused_wall / 1e12, 4
                )
                if self.peak_flops and fused_flops > 0:
                    fused_out["mfu_decode"] = round(
                        fused_flops / fused_wall / self.peak_flops, 5
                    )
            out["fused"] = fused_out
        if spec:
            spec_wall = spec_totals["wall"]
            spec_out: dict[str, Any] = {
                "requests_profiled": spec,
                "tokens": spec_tokens,
                "rounds": spec_rounds,
                "overlapped_rounds": spec_overlapped,
                "overlap_fraction": (
                    round(spec_overlapped / spec_rounds, 4)
                    if spec_rounds > 0
                    else 0.0
                ),
                "wall_ms_total": round(spec_wall * 1000.0, 3),
                "segments_ms_total": {
                    name: round(spec_totals[name] * 1000.0, 3)
                    for name in SPEC_SEGMENTS
                },
                "segment_frac": {
                    name: (
                        round(spec_totals[name] / spec_wall, 4)
                        if spec_wall > 0
                        else 0.0
                    )
                    for name in SPEC_SEGMENTS
                },
                "ring": spec_ring,
            }
            if spec_wall > 0:
                spec_out["tokens_per_s"] = round(spec_tokens / spec_wall, 1)
            out["spec"] = spec_out
        if flow_done > 0:
            out["dispatches_per_decision"] = round(
                flow_disp / flow_done, 4
            )
        if tpd is not None:
            out["prefill_tokens_per_decision"] = round(tpd, 2)
        return out

    def gauges(self) -> dict[str, float]:
        """Flat numeric view for /metrics (observability/metrics._flatten
        renders each as a llm_scheduler_engine_profile_* gauge)."""
        with self._lock:
            totals = dict(self._totals)
            cum = dict(self._cum)
            flops = self._flops_total
            waves = self.waves_profiled
            pack_totals = dict(self._pack_totals)
            packs = self.packs_profiled
            fused_totals = dict(self._fused_totals)
            fused_flops = self._fused_flops
            fused = self.fused_profiled
            spec_totals = dict(self._spec_totals)
            spec_rounds = self._spec_rounds
            spec_overlapped = self._spec_overlapped
            spec = self.spec_profiled
            flow_disp = self._flow_dispatches
            flow_done = self._flow_decisions
            tpd = self._prefill_tokens_per_decision_locked()
        wall = totals["wall"]
        out: dict[str, float] = {"waves_profiled": float(waves)}
        for name in SEGMENTS:
            out[f"{name}_frac"] = (
                round(totals[name] / wall, 4) if wall > 0 else 0.0
            )
            # monotone ms counters (module __init__ comment): what the
            # SLO engine's windowed deltas consume
            out[f"{name}_ms_total"] = round(cum[name] * 1000.0, 3)
        out["wall_ms_cum_total"] = round(cum["wall"] * 1000.0, 3)
        if packs:
            out["packs_profiled"] = float(packs)
            pack_wall = pack_totals["wall"]
            for name in PACK_SEGMENTS:
                out[f"pack_{name}_frac"] = (
                    round(pack_totals[name] / pack_wall, 4)
                    if pack_wall > 0
                    else 0.0
                )
        if fused:
            out["fused_profiled"] = float(fused)
            fused_wall = fused_totals["wall"]
            for name in FUSED_SEGMENTS:
                out[f"fused_{name}_frac"] = (
                    round(fused_totals[name] / fused_wall, 4)
                    if fused_wall > 0
                    else 0.0
                )
            if self.peak_flops and fused_wall > 0 and fused_flops > 0:
                out["fused_mfu_decode"] = round(
                    fused_flops / fused_wall / self.peak_flops, 5
                )
        if spec:
            out["spec_profiled"] = float(spec)
            spec_wall = spec_totals["wall"]
            for name in SPEC_SEGMENTS:
                out[f"spec_{name}_frac"] = (
                    round(spec_totals[name] / spec_wall, 4)
                    if spec_wall > 0
                    else 0.0
                )
            out["spec_overlap_frac"] = (
                round(spec_overlapped / spec_rounds, 4)
                if spec_rounds > 0
                else 0.0
            )
        if flow_done > 0:
            out["dispatches_per_decision"] = round(
                flow_disp / flow_done, 4
            )
        if tpd is not None:
            out["prefill_tokens_per_decision"] = round(tpd, 2)
        out["device_compute_frac"] = (
            round(totals["device_compute"] / wall, 4) if wall > 0 else 0.0
        )
        if wall > 0:
            out["achieved_tflops"] = round(flops / wall / 1e12, 4)
        mfu = self._mfu(flops, wall, totals["device_compute"], totals)
        if mfu is not None:
            out["mfu_decode"] = mfu["decode"]
            if "device" in mfu:
                out["mfu_device"] = mfu["device"]
            for name, value in (mfu.get("loss") or {}).items():
                out[f"mfu_loss_{name}"] = value
        return out

    def close(self) -> None:
        """Flush any in-flight fence state (waves that will never harvest —
        backend shutdown fails them upstream) so shutdown leaves no
        half-open records; idempotent."""
        with self._lock:
            self._open.clear()
            self.closed = True
