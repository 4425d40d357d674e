"""LocalLLMBackend — the in-tree TPU decision backend with continuous
batching.

This implements the DecisionBackend seam (engine/backend.py) with a real
model: prompts built by core/prompt.py, decoded by engine/engine.py under a
node-name grammar (engine/constrained.py). It replaces the reference's
HuggingFaceClient._make_api_call (reference scheduler.py:418-433) — same
inputs (pod, cluster state), same output (a SchedulingDecision), zero
network.

Concurrency model: DecisionClient calls get_scheduling_decision from worker
threads (one per in-flight pod, via asyncio.to_thread). Those calls enqueue
a request and block on a Future. A single engine-owner thread drains the
queue and drives the InferenceEngine with PIPELINED DECISION WAVES
(engine.submit_wave / harvest_wave): each wave is one fused device program
(suffix prefill + full constrained decode, no paged-cache traffic), and the
worker keeps submitting waves while earlier ones are still executing — the
per-dispatch round-trip latency overlaps across waves instead of
serializing. While waiting on the
oldest wave's results it polls the queue, so stragglers of a burst join the
next pipelined wave rather than stalling behind a blocking sync.

Group keying: the engine holds ONE (prompt prefix, grammar) pair at a time,
both keyed by the cluster snapshot — the prefix is the burst-shared
(system + cluster state) token block (core/prompt.py split_prompt), the
grammar is the DFA over the snapshot's ready node names. Requests group by
that pair; a new group installs its prefix KV + DFA only when the engine
drains. Within a burst (shared snapshot — the reference's own cache-key
equivalence, scheduler.py:265-271) everything lands in one group.
"""

from __future__ import annotations

import asyncio
import functools
import logging
import queue
import threading
import time
from collections import deque
from collections.abc import Sequence
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FuturesTimeout
from pathlib import Path
from types import SimpleNamespace
from typing import Any

import jax

from k8s_llm_scheduler_tpu.core.prompt import PromptEngine, pod_suffix
from k8s_llm_scheduler_tpu.core.validation import feasible_nodes
from k8s_llm_scheduler_tpu.observability import spans
from k8s_llm_scheduler_tpu.engine.backend import BackendError, NoFeasibleNodeError
from k8s_llm_scheduler_tpu.engine.constrained import build_decision_dfa
from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer, Tokenizer
from k8s_llm_scheduler_tpu.models import family
from k8s_llm_scheduler_tpu.models.configs import LlamaConfig, get_config
from k8s_llm_scheduler_tpu.parallel.mesh import mesh_from_config
from k8s_llm_scheduler_tpu.parallel.sharding import (
    named_shardings,
    param_specs,
    shard_params,
    validate_specs_divisibility,
)
from k8s_llm_scheduler_tpu.types import (
    DecisionSource,
    NodeMetrics,
    PodSpec,
    SchedulingDecision,
)
from k8s_llm_scheduler_tpu.utils.compile_cache import (
    COMPILE_LOG,
    enable_persistent_compile_cache,
)
from k8s_llm_scheduler_tpu.utils.json_extract import parse_decision_json

logger = logging.getLogger(__name__)


class _WorkItem:
    __slots__ = (
        "prefix_ids", "suffix_ids", "group_key", "future", "enqueued_at",
        "enqueued_wall", "trace", "pack", "pin_spec",
    )

    def __init__(self, prefix_ids, suffix_ids, group_key):
        self.prefix_ids = prefix_ids
        self.suffix_ids = suffix_ids
        self.group_key = group_key  # (prefix token tuple, grammar names) pair
        self.future: Future = Future()
        self.enqueued_at = time.perf_counter()
        # Batch-surface marker (get_scheduling_decisions_batch): items
        # sharing a pack marker arrived as ONE admission batch and route
        # through the engine's packed chunked admission
        # (engine.admit_packed) instead of wave rows — the engine-side
        # half of the fleet prepack mechanism (fleet/pools.py).
        self.pack = None
        # (pin key, pinned-prefix token ids) when the prompt is
        # delta-encoded (sched/delta.py): the worker pins the snapshot
        # prefix KV before installing the group so the delta-extended
        # prefix LCP-seeds from it.
        self.pin_spec = None
        # wall-clock twin of enqueued_at: retroactive flight-recorder spans
        # are wall-anchored (observability/spans), while all durations stay
        # perf_counter deltas
        self.enqueued_wall = time.time()  # graftlint: ok[raw-clock] — wall anchor for cross-process span stitching, not a judgment
        # (Trace, SpanContext) captured on the SUBMITTING thread — the
        # engine worker attaches admission-wait/prefill/decode spans to it
        # at harvest. None when no trace is ambient (tracing off, prewarms).
        self.trace = None

    def resolve(self, text: str) -> None:
        """Set the result unless the caller already cancelled/timed out —
        the async client path (get_scheduling_decision_async) cancels the
        underlying future via asyncio.wrap_future, and a bare set_result
        would raise InvalidStateError and take down the whole worker tick."""
        if not self.future.done():
            self.future.set_result(text)

    def fail(self, exc: Exception) -> None:
        if not self.future.done():
            self.future.set_exception(exc)


class _ControlItem:
    """An engine-owner-thread control action (rollout/hotswap.py weight
    swaps) queued alongside work items. The worker HOLDS all admissions
    while one is pending and executes it only at a wave barrier (every
    in-flight wave harvested) — the quiesce point a zero-downtime weight
    swap needs. The future resolves to (fn result, pause_s) where pause_s
    is the admission-held wall time: enqueue -> barrier drained -> fn done."""

    __slots__ = ("fn", "future", "enqueued_at")

    def __init__(self, fn):
        self.fn = fn
        self.future: Future = Future()
        self.enqueued_at = time.perf_counter()

    def fail(self, exc: Exception) -> None:
        if not self.future.done():
            self.future.set_exception(exc)


class LocalLLMBackend:
    """DecisionBackend over an in-process InferenceEngine."""

    def __init__(
        self,
        engine: InferenceEngine,
        tokenizer: Tokenizer | None = None,
        max_new_tokens: int = 200,
        constrained: bool = True,
        request_timeout_s: float = 60.0,
        admit_wait_s: float = 0.002,
        group_switch_after_s: float = 0.25,
        partial_hold_s: float = 0.03,
        prewarm_idle_delay_s: float = 0.5,
        answer_style: str = "direct",
        max_reason_tokens: int = 320,
        pool_role: str = "mixed",
        packed_admission: bool = True,
        delta_prompts: bool = False,
        repin_fraction: float = 0.25,
        max_pins: int = 4,
    ) -> None:
        self.engine = engine
        # seconds by set-up span (`engine.setup_build`, `engine.setup_params`
        # and, inside the latter, `engine.setup_tokenizer`, `engine.setup_wait`),
        # filled by build_local_backend; empty for a backend built otherwise
        self.setup: dict[str, float] = {}
        # Admission plane (engine/admission/): batch-surface decisions
        # admit via packed chunked prefill when the engine supports it;
        # delta_prompts renders cluster prefixes as pinned snapshot +
        # drift diff (sched/delta.py) so prefill scales with what changed.
        # (an engine whose model has no paged forwards keeps the batch
        # surface on waves, as a lone marked straggler already rides one)
        self._packed_admission = (
            bool(packed_admission)
            and hasattr(engine, "admit_packed")
            and getattr(engine, "paged", True)
        )
        if delta_prompts:
            from k8s_llm_scheduler_tpu.sched.delta import SnapshotDeltaEncoder

            self._delta = SnapshotDeltaEncoder(repin_fraction=repin_fraction)
        else:
            self._delta = None
        # (pin_key, token ids) of the last pinned snapshot prefix — one
        # tokenize per pin, not per decision (GIL-atomic tuple swap).
        self._pin_ids_cache: tuple | None = None
        if hasattr(engine, "pin_prefix"):
            from k8s_llm_scheduler_tpu.engine.admission.pinned import (
                PinnedPrefixManager,
            )

            self._pin_manager = PinnedPrefixManager(engine, max_pins=max_pins)
        else:  # engine test doubles
            self._pin_manager = None
        # Shared prefix-KV plane client, attached post-construction by
        # the fleet (attach_kvplane) — None means pins are purely local.
        self._kvplane = None
        # Disaggregated-pool role (fleet/pools.py): "decode" workers
        # refuse admission (work="prefill") so a fleet routing bug fails
        # loudly instead of letting admission bursts evict the decode
        # pool's throughput; "prefill"/"mixed" accept everything.
        if pool_role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"pool_role {pool_role!r} not in ('prefill', 'decode', 'mixed')"
            )
        self.pool_role = pool_role
        self.role_refusals = 0  # GIL-atomic counter (stats only)
        # Decision JSON field order: "direct" (reference serialization) or
        # "cot" (reasoning emitted BEFORE the constrained node choice —
        # engine/constrained.py). The parsed object is identical.
        self.answer_style = answer_style
        # Cap on the reasoning field's token budget (the DFA bound; the
        # effective cap is min(this, max_new_tokens - 62 - name)). The
        # scratchpad CoT of a distilled checkpoint (train/distill.build_cot
        # with input echoes) measures <=245 tokens at 5 feasible nodes
        # numeric-tokenized, <=290 byte-tokenized — CoT serving needs
        # max_new_tokens ~390 alongside the 320 default here.
        self.max_reason_tokens = max_reason_tokens
        # Idle grace before a sibling-geometry prewarm compile may start:
        # a jit blocks the worker for seconds, so it must not fire the
        # instant the queue empties — a burst's next round often arrives
        # within ms (measured: a prewarm starting between bench rounds
        # delayed the next round's waves 9s behind its compile).
        self.prewarm_idle_delay_s = prewarm_idle_delay_s
        # Max time a ragged wave tail may wait for stragglers while earlier
        # waves are in flight (see _submit_waves.run_group).
        self.partial_hold_s = partial_hold_s
        self.tokenizer = tokenizer or engine.tokenizer
        self.prompt_engine = PromptEngine()
        self.max_new_tokens = max_new_tokens
        # Fairness bound for (prefix, grammar) group switches under load —
        # see _submit_waves.
        self.group_switch_after_s = group_switch_after_s
        # Sparse DFA tables are vocab-independent (engine/constrained.py
        # SparseDFATables), so constrained decoding works at any vocab size
        # — including 128k-vocab BPE tokenizers for real checkpoints.
        self.constrained = constrained
        self.request_timeout_s = request_timeout_s
        self.admit_wait_s = admit_wait_s
        self._queue: queue.Queue[_WorkItem | None] = queue.Queue()
        self._dfa_cache: dict[tuple[str, ...], Any] = {}
        self._current_group: tuple | None = None
        # group key -> `enqueued_at` of the first item that carried it: the
        # order in which snapshots first reached this backend, which is all
        # a backend knows of their age (_submit_waves moves a tail only
        # into a snapshot that reached it LATER)
        self._group_first_seen: dict[tuple, float] = {}
        # Control items (run_quiesced) parked until the wave barrier; while
        # any is held, _submit_waves admits nothing (swap quiesce).
        self._held_controls: list[_ControlItem] = []
        # Rolling swap-pause bookkeeping surfaced via get_stats/metrics.
        self.swap_stats = {
            "quiesce_runs": 0,
            "last_pause_s": 0.0,
            "total_pause_s": 0.0,
        }
        # EMA of per-wave device service time, used to DEADLINE the
        # is_ready() straggler-poll in _worker_tick. The rule: is_ready()
        # is trusted only up to the moment this wave SHOULD be done — a
        # runtime may flip it when the whole enqueued chain drains rather
        # than when this wave's result landed, and trusting that defers
        # every leader by the full pipeline depth. A blocking harvest
        # returns at true completion; the EMA tells us when polling
        # stops being useful. Keyed PER GEOMETRY
        # (WaveHandle.geo_key): a 50ms half-R decision wave and a 2s
        # full-R longctx wave alternating in one workload must not share
        # an estimate — the fast-down update would chronically
        # under-deadline the long one and serialize its pipeline.
        self._wave_ema: dict[tuple | None, float] = {}
        self._wave_ema_default = 0.5
        self._last_harvest_t = 0.0
        self._worker = threading.Thread(
            target=self._run_worker, daemon=True, name="llm-engine"
        )
        self._stopped = threading.Event()
        self._worker.start()

    # ------------------------------------------------------------- backend
    def _cluster_part(self, nodes: Sequence[NodeMetrics]):
        """(cluster_part text, pin_spec | None, delta_nodes) — THE single
        rendering seam for real decisions and prewarms: with delta
        encoding on, both land on the identical pinned-snapshot + diff
        text (one group key); off, both use the plain full render."""
        if self._delta is None:
            return self.prompt_engine.cluster_part(nodes), None, 0
        with spans.span("delta_encode", layer="sched"):
            dp = self._delta.encode(nodes)
        pin_spec = None
        if dp.pin_key is not None:
            cached = self._pin_ids_cache
            if cached is not None and cached[0] == dp.pin_key:
                pin_ids = cached[1]
            else:
                # The pin's token ids as rendered in chat format (same
                # stand-in-suffix trick as _prepare_prewarm: the prefix
                # depends only on (system, cluster_part)).
                pin_ids, _ = self.tokenizer.chat_prompt_parts(
                    self.prompt_engine.system_prompt, dp.pin_text, "x"
                )
                self._pin_ids_cache = (dp.pin_key, pin_ids)
            pin_spec = (dp.pin_key, pin_ids)
        return dp.cluster_part, pin_spec, dp.delta_nodes

    def _prepare_item(
        self, pod: PodSpec, nodes: Sequence[NodeMetrics],
        cluster_info: tuple | None = None,
    ) -> _WorkItem:
        """`cluster_info` is a precomputed _cluster_part result: the batch
        surface passes one per decide_batch frame so a B-pod pack does ONE
        cluster render/diff instead of B identical ones."""
        candidates = feasible_nodes(pod, nodes)
        if not candidates:
            raise NoFeasibleNodeError(
                f"no feasible node for {pod.namespace}/{pod.name}"
            )
        # render / delta_encode / tokenize: the caller's synchronous host
        # work per decision, on the caller's thread (the event loop under
        # Scheduler.run) — hence layer "sched", not "engine"
        with spans.span("render", layer="sched"):
            cluster_part, pin_spec, delta_nodes = (
                cluster_info if cluster_info is not None
                else self._cluster_part(nodes)
            )
            pod_part = pod_suffix(pod)
        with spans.span("tokenize", layer="sched"):
            prefix_ids, suffix_ids = self.tokenizer.chat_prompt_parts(
                self.prompt_engine.system_prompt, cluster_part, pod_part
            )
        # Grammar over READY nodes of this snapshot (stable across the pods
        # of a burst); per-pod feasibility is enforced by validation upstream.
        ready_names = tuple(sorted(n.name for n in nodes if n.is_ready))
        group_key = (
            tuple(prefix_ids),
            ready_names if self.constrained else None,
        )
        item = _WorkItem(prefix_ids, suffix_ids, group_key)
        item.pin_spec = pin_spec
        item.trace = spans.capture()
        if self._delta is not None:
            trace = spans.current_trace()
            if trace is not None:
                trace.set_meta(
                    prompt_encoding="delta" if delta_nodes else "pinned",
                    delta_nodes=delta_nodes,
                )
        return item

    def prewarm_prefix(self, nodes: Sequence[NodeMetrics]) -> Future:
        """Advisory: install this snapshot's (prefix KV, grammar) group
        while the engine is idle, so the FIRST wave of the next burst
        skips the chunked cluster-state prefill (~145 ms at 1B/64 nodes —
        the dominant term in SCALING.md's burst1000 floor decomposition).

        Returns a Future resolving True if the group was installed (or
        already current), False if dropped — the engine was busy (real
        traffic decides groups; an advisory must never preempt a wave or
        force a switch mid-burst) or the snapshot had no ready nodes.
        Thread-safe; never blocks the caller.

        The prefix tokens are built exactly as _prepare_item builds them
        for a real pod — the pod part only ever lands in the suffix — so
        a subsequent burst on the same snapshot matches this group key
        and pays zero prefix cost."""
        item = self._prepare_prewarm(nodes)
        if item is None:
            f: Future = Future()
            f.set_result(False)
            return f
        self._queue.put(item)
        return item.future

    def _prepare_prewarm(self, nodes: Sequence[NodeMetrics]):
        ready_names = tuple(sorted(n.name for n in nodes if n.is_ready))
        if not ready_names:
            return None
        cluster_part, pin_spec, _ = self._cluster_part(nodes)
        # Any non-empty stand-in suffix yields the identical prefix ids:
        # chat_prompt_parts splits at the end of the user-prefix string,
        # so the prefix depends only on (system, cluster_part). An EMPTY
        # suffix would degrade the HF adapter to no-split (prefix []).
        prefix_ids, _ = self.tokenizer.chat_prompt_parts(
            self.prompt_engine.system_prompt, cluster_part, "x"
        )
        group_key = (
            tuple(prefix_ids),
            ready_names if self.constrained else None,
        )
        item = _WorkItem(prefix_ids, None, group_key)
        item.pin_spec = pin_spec
        return item

    def _check_role(self, work: str) -> None:
        """Pool-role admission gate (fleet/pools.check_pool_role
        semantics, inlined to keep engine imports fleet-free): a
        decode-role worker refuses prefill (admission) work."""
        if self.pool_role == "decode" and work == "prefill":
            self.role_refusals += 1
            raise BackendError(
                "pool role 'decode' refuses admission (prefill) work — "
                "route new-snapshot decisions to the prefill pool"
            )

    def get_scheduling_decision(
        self, pod: PodSpec, nodes: Sequence[NodeMetrics],
        work: str = "prefill",
    ) -> SchedulingDecision:
        self._check_role(work)
        item = self._prepare_item(pod, nodes)
        self._queue.put(item)
        try:
            text = item.future.result(timeout=self.request_timeout_s)
        except FuturesTimeout as exc:
            # (concurrent.futures.TimeoutError only aliases the builtin from
            # Python 3.11 — catch the futures one for 3.10.)
            raise BackendError(f"decision timed out after {self.request_timeout_s}s") from exc
        return self._parse(text, pod)

    def get_scheduling_decisions_batch(
        self, pods: Sequence[PodSpec], nodes: Sequence[NodeMetrics],
        work: str = "prefill",
    ) -> list["SchedulingDecision | Exception"]:
        """Prepacked admission (fleet/pools.py): enqueue the WHOLE pack
        before waiting on any future, so the engine worker admits the
        batch together and coalesces it into one prefill wave (many
        short scheduler prompts, one shared cluster prefix — the
        Prepacking economics). Per-pod outcomes are returned
        positionally (decision or exception); one infeasible pod never
        fails its batchmates."""
        self._check_role(work)
        staged: list[tuple[int, "_WorkItem"]] = []
        out: list[SchedulingDecision | Exception] = [
            BackendError("batch slot unresolved")
        ] * len(pods)
        # One marker per batch call: the worker routes marked items of a
        # group through engine.admit_packed (packed block-diagonal
        # prefill) instead of wave rows — the wire-level decide_batch
        # frame (fleet/pools.py prepack) and the engine-level pack are
        # ONE mechanism, with no second whole-prompt prefill.
        pack_marker = object() if self._packed_admission else None
        cluster_info = self._cluster_part(nodes)  # once per frame, not per pod
        for i, pod in enumerate(pods):
            try:
                item = self._prepare_item(pod, nodes, cluster_info=cluster_info)
            except Exception as exc:  # NoFeasibleNodeError, tokenizer...
                out[i] = exc
                continue
            item.pack = pack_marker
            staged.append((i, item))
        for _, item in staged:
            self._queue.put(item)
        for i, item in staged:
            try:
                text = item.future.result(timeout=self.request_timeout_s)
                out[i] = self._parse(text, pods[i])
            except FuturesTimeout:
                out[i] = BackendError(
                    f"decision timed out after {self.request_timeout_s}s"
                )
            except Exception as exc:
                out[i] = exc
        return out

    async def get_scheduling_decision_async(
        self, pod: PodSpec, nodes: Sequence[NodeMetrics],
        work: str = "prefill",
    ) -> SchedulingDecision:
        """Natively-async decision: awaits the engine future WITHOUT holding
        a worker thread. With the sync path, every in-flight pod pins one
        asyncio.to_thread pool thread for the whole wave round trip — a
        burst with more distinct pod shapes than pool threads
        (min(32, cpus+4) by default) deadlocks the burst into serial waves.
        DecisionClient prefers this method when present."""
        self._check_role(work)
        item = self._prepare_item(pod, nodes)
        self._queue.put(item)
        try:
            text = await asyncio.wait_for(
                asyncio.wrap_future(item.future), timeout=self.request_timeout_s
            )
        except (TimeoutError, asyncio.TimeoutError) as exc:
            raise BackendError(
                f"decision timed out after {self.request_timeout_s}s"
            ) from exc
        return self._parse(text, pod)

    def _parse(self, text: str, pod: PodSpec) -> SchedulingDecision:
        parsed = parse_decision_json(text)
        if parsed is None:
            raise BackendError(f"model produced unparseable decision: {text[:200]!r}")
        return SchedulingDecision(
            selected_node=parsed["selected_node"],
            confidence=parsed["confidence"],
            reasoning=parsed["reasoning"],
            source=DecisionSource.LLM,
        )

    # -------------------------------------------------------------- worker
    def _grammar_for(self, key: tuple[str, ...]):
        if key not in self._dfa_cache:
            if len(self._dfa_cache) > 16:
                self._dfa_cache.clear()
            # The whole emission must fit in max_new_tokens or the decode
            # truncates mid-JSON. Worst case emission =
            #   len('{"selected_node": ""') + name + len(', "confidence": 0.00')
            #   + len(', "reasoning": ""}') + EOS + reasoning
            # = 59 + name_tokens + 1 + reasoning. No floor: an empty
            # reasoning is grammatical; a floor here broke the guarantee.
            longest_name = max(len(self.tokenizer.encode(n)) for n in key)
            budget = self.max_new_tokens - (60 + longest_name) - 2  # margin
            if budget < 0:
                raise ValueError(
                    f"max_new_tokens={self.max_new_tokens} cannot fit even an "
                    f"empty decision for node names up to {longest_name} tokens; "
                    f"need >= {62 + longest_name}"
                )
            effective = min(budget, self.max_reason_tokens)
            if self.answer_style == "cot" and effective < self.max_reason_tokens:
                # Silent truncation burns distilled-checkpoint quality: the
                # scratchpad gets force-closed mid-comparison and the
                # constrained choice runs off a half-built argument
                # (measured: eval agreement 40/40 -> 44% from exactly
                # this). One loud line beats a quiet quality cliff.
                logger.warning(
                    "answer_style=cot but max_new_tokens=%d caps reasoning "
                    "at %d tokens (< max_reason_tokens=%d) — scratchpads "
                    "for larger clusters will be truncated; raise "
                    "llm.max_tokens to >= %d",
                    self.max_new_tokens, effective, self.max_reason_tokens,
                    # exact floor: budget = max_new - (60 + name) - 2, so
                    # budget >= max_reason_tokens at 62 + name + reason
                    self.max_reason_tokens + 62 + longest_name,
                )
            self._dfa_cache[key] = build_decision_dfa(
                self.tokenizer, list(key),
                max_reason_tokens=effective,
                style=self.answer_style,
            )
        return self._dfa_cache[key]

    def _install_group(self, item: _WorkItem) -> None:
        """Install item's (prefix, grammar) group on the engine. With a
        delta-encoded prompt, the snapshot prefix is PINNED first
        (admission/pinned.py) so set_prefix LCP-seeds from the pin and
        prefills only the delta tail — the O(changed) admission cost.
        When a kvplane client is attached, the pin may ADOPT a peer
        replica's pages instead of prefilling; the provenance lands on
        the decision trace as kv_source."""
        with spans.thread_span("install_group", layer="engine"):
            if item.pin_spec is not None and self._pin_manager is not None:
                key, pin_ids = item.pin_spec
                try:
                    self._pin_manager.ensure(key, pin_ids)
                except Exception:
                    # unpinned is slower, never wrong — the group install
                    # below still prefills the full prefix
                    logger.exception("snapshot prefix pin failed; continuing")
                if item.trace is not None:
                    src = self._pin_manager.source_of(key)
                    if src is not None:
                        item.trace[0].set_meta(kv_source=src)
            self.engine.set_prefix(item.prefix_ids)
            names = item.group_key[1]
            self.engine.set_grammar(
                self._grammar_for(names) if names is not None else None
            )

    def _submit_pack(
        self, batch: list[_WorkItem], packs: "list[dict]"
    ) -> None:
        """Admit a marked batch through the engine's packed chunked
        admission (engine.admit_packed); decode is driven by
        _drive_packs at each tick."""
        try:
            req_ids = self.engine.admit_packed(
                [i.suffix_ids for i in batch], self.max_new_tokens
            )
        except Exception as exc:
            for item in batch:
                item.fail(BackendError(str(exc)))
        else:
            packs.append({
                "items": dict(zip(req_ids, batch)),
                "submitted_at": time.perf_counter(),
            })

    def _submit_waves(
        self,
        pending: list[_WorkItem],
        waves: "deque[tuple[Any, list[_WorkItem]]]",
        packs: "list[dict]",
    ) -> list[_WorkItem]:
        """Dispatch every admissible pending item as pipelined waves.

        Items group by (prefix, grammar). A wave captures its prefix buffers
        and grammar tables BY REFERENCE at submit, so repointing the engine
        at another group while waves are in flight is device-safe (the new
        prefix's prefill simply queues behind the outstanding waves; only
        the chunked slot path requires a drain, and set_prefix guards it).
        Switching still costs a prefill dispatch and sparse-table upload, so
        under load it happens at most once per tick and only when the
        other group's oldest item has waited group_switch_after_s — a
        fairness bound: interleaved snapshots round-robin at that period
        instead of starving behind a sustained hot group until the request
        timeout (60 s).

        Returns items that must keep waiting (held ragged tails, other
        groups not yet switched to).
        """
        controls = [i for i in pending if isinstance(i, _ControlItem)]
        if controls:
            self._held_controls.extend(controls)
            pending = [i for i in pending if not isinstance(i, _ControlItem)]
        if self._held_controls:
            # Quiesce in progress: hold EVERY admission (work and prewarms
            # alike) until the control runs at the wave barrier
            # (_worker_tick). The held wall time is the swap pause metric.
            return list(pending)
        if any(i.suffix_ids is None for i in pending):
            # Advisory prefix installs (prewarm_prefix) are diverted HERE —
            # the single consumer of `pending` — because the coalescing and
            # straggler-poll loops both drain the queue mid-tick and can
            # hand this function a prewarm at any point. Only the LATEST
            # snapshot matters, and it applies only when the engine is
            # genuinely idle: real traffic always decides groups.
            prewarms = [i for i in pending if i.suffix_ids is None]
            pending = [i for i in pending if i.suffix_ids is not None]
            for stale in prewarms[:-1]:
                stale.resolve(False)
            latest = prewarms[-1]
            if latest.group_key == self._current_group:
                latest.resolve(True)
            elif pending or waves or packs:
                latest.resolve(False)
            else:
                self._current_group = None
                try:
                    self._install_group(latest)
                    self._current_group = latest.group_key
                    latest.resolve(True)
                except Exception:
                    logger.exception("prefix prewarm failed")
                    latest.resolve(False)
        rest: list[_WorkItem] = []

        def owed() -> bool:
            """The oldest wave in flight has finished and nobody has its
            answers yet. They come first: every dispatch BLOCKS while the
            device's queue is full (engine.submit_wave, set_prefix), for as
            long as the wave on the device still runs, and this thread is
            the one that harvests. A standing backlog hands a harvest's
            rows straight back as the next wave, so four waves' rows once
            come back together, cost four blocking dispatches in a row
            while four more waves finish unharvested, and come back
            together for good: measured on the v5e (PERF.md §6, PR 34,
            second session) binds arrived 32 at a time with 1.0 and 1.6 s
            between, every wave harvested a second after it had finished.
            What is not dispatched now stays in `pending`, is dispatched
            next tick behind the harvest, and the device's queue stays as
            deep as the runtime lets it. is_ready() that flips late (see
            _wave_ema) only leaves things as they were."""
            # getattr: the policy tests hand in bare objects as handles
            ready = getattr(waves[0][0], "is_ready", None) if waves else None
            return ready is not None and ready()

        def submit(batch: list[_WorkItem]) -> None:
            if owed():
                rest.extend(batch)
                return
            try:
                handle = self.engine.submit_wave(
                    [i.suffix_ids for i in batch], self.max_new_tokens
                )
            except Exception as exc:  # bucket overflow, bad grammar state
                for item in batch:
                    item.fail(BackendError(str(exc)))
            else:
                # getattr: engine test doubles don't carry the attribute
                prof = getattr(self.engine, "profiler", None)
                if prof is not None:
                    # queue-stall fence: the oldest batch item's enqueue is
                    # the wave's timeline anchor (admission wait + coalesce
                    # window + group-switch fairness holds all land here)
                    prof.note_admission(
                        handle, min(i.enqueued_at for i in batch)
                    )
                waves.append((handle, batch))

        def run_group(items: list[_WorkItem], leaving: bool = False) -> None:
            """Full waves submit; a ragged tail holds while the pipeline
            is busy. While a wave executes, more of the burst's leaders
            keep arriving — holding the partial turns seven ragged waves
            into two full ones. Behind ONE wave in flight the hold is
            deadline-bounded: once the tail has waited ~partial_hold_s it
            ships as-is — an unbounded hold parks the tail for a FULL
            wave round trip (~230ms measured), pushing its followers past
            every other pod in the burst. Behind TWO or more it holds
            until it fills or the pipeline runs down to one: the device
            runs waves one after another, so a wave submitted now starts
            no sooner than one submitted when only the executing wave is
            left, and a ragged wave costs the device what a full one does.
            (A standing backlog hands each harvest's rows back as the
            next wave's: a 7+1 split shipped on a deadline came round
            again as 7+1 for good, an eighth of the device's time on one
            row.) `leaving` = a group switch is due this tick: nothing
            more will batch with this tail, so it ships.

            Pack-marked items (a decide_batch admission batch) route
            through engine.admit_packed instead: one packed
            block-diagonal prefill for the whole batch, bounded by the
            engine's free paged slots (leftovers wait for slots to
            drain). A lone marked straggler just rides a wave."""
            if self._packed_admission:
                # The paged pack path is page-table-bounded, tighter than
                # the wave bound: an oversized suffix rides a wave rather
                # than failing its pack (or poisoning its batchmates).
                try:
                    pack_limit = self.engine.max_suffix_tokens(
                        self.max_new_tokens
                    )
                except AttributeError:  # engine test doubles
                    pack_limit = self.engine.prefill_buckets[-1]
                packable = [
                    i for i in items
                    if i.pack is not None and len(i.suffix_ids) <= pack_limit
                ]
                if len(packable) >= 2:
                    free = self.engine.free_slots
                    if free >= 2:
                        batch = packable[:free]
                        self._submit_pack(batch, packs)
                        rest.extend(packable[len(batch):])
                    else:
                        # no slots yet: wait for in-flight packs to drain
                        rest.extend(packable)
                    handled = set(map(id, packable))
                    items = [i for i in items if id(i) not in handled]
            batch: list[_WorkItem] = []
            for item in items:
                batch.append(item)
                if len(batch) >= self.engine.max_slots:
                    submit(batch)
                    batch = []
            if batch:
                oldest = min(i.enqueued_at for i in batch)
                held_s = time.perf_counter() - oldest
                if not leaving and (
                    len(waves) >= 2
                    or (waves and held_s < self.partial_hold_s)
                ):
                    rest.extend(batch)
                else:
                    submit(batch)

        current: list[_WorkItem] = []
        others: list[_WorkItem] = []
        seen = self._group_first_seen
        for item in pending:
            if item.group_key not in seen:
                while len(seen) >= 64:  # insertion order: the oldest goes
                    del seen[next(iter(seen))]
                seen[item.group_key] = item.enqueued_at
            if len(item.suffix_ids) > self.engine.prefill_buckets[-1]:
                # Oversized suffix can never admit (waves are bounded only by
                # the largest prefill bucket — they never touch the paged
                # cache) — fail it alone instead of poisoning its whole wave.
                item.fail(
                    BackendError(
                        f"pod prompt suffix of {len(item.suffix_ids)} tokens "
                        f"exceeds the largest prefill bucket "
                        f"{self.engine.prefill_buckets[-1]}"
                    )
                )
            elif item.group_key == self._current_group:
                current.append(item)
            else:
                others.append(item)

        # ONE reading decides both: a switch whose fairness wait is over
        # happens below, so the current group's tail must not hold for it
        oldest = min(others, key=lambda i: i.enqueued_at, default=None)
        waited = time.perf_counter() - oldest.enqueued_at if others else 0.0
        leaving = (
            bool(others) and not packs
            and waited >= self.group_switch_after_s
            and not owed()
        )
        # The ragged tail the engine would leave behind goes WITH it where
        # the group it switches to is another snapshot of the same cluster
        # (the same ready nodes, so the same grammar and the same valid
        # answers: only the metrics in the prefix differ, and the pod's own
        # part of the prompt is the suffix, which no snapshot touches). A
        # snapshot that changed between two pods of one burst otherwise
        # costs a wave: k rows ship ragged here and the other 8 - k lead
        # the new group ragged (a wave's eight binds release eight pods
        # that reach the scheduler's snapshot over some tens of
        # milliseconds, and its TTL runs out among them about one time in
        # three; measured on the v5e, PERF.md §6 PR 30: 13-23 extra waves
        # of ~320 a window, by luck). Pods of ANOTHER cluster (other ready
        # nodes) are never moved, and a tail moves only FORWARD: into a
        # snapshot that first reached this backend after its own did, so a
        # pod is never decided on older metrics than it was encoded under
        # (a straggler of an earlier snapshot takes no tail with it).
        adopted: list[_WorkItem] = []
        ragged = len(current) % self.engine.max_slots
        if (
            leaving and ragged
            and oldest.group_key[1] is not None  # a grammar names the nodes
            and oldest.group_key[1] == current[-1].group_key[1]
            and seen[oldest.group_key] > seen[current[-1].group_key]
            and all(i.pack is None for i in current)
        ):
            current, adopted = current[:-ragged], current[-ragged:]
        run_group(current, leaving=leaving)
        if not others:
            return rest

        if packs:
            # Paged slots are mid-flight against the CURRENT prefix
            # pointer — set_prefix requires a drained engine, so a group
            # switch must wait for the packs to finish decoding
            # (bounded: the device-side budget guarantees completion).
            rest.extend(others)
            return rest
        if owed() or (waves and waited < self.group_switch_after_s):
            # (a switch dispatches a prefix prefill: a finished wave's
            # harvest goes before it too; a tail cut off above for the
            # switch stays where it was)
            rest.extend(adopted)
            rest.extend(others)
            return rest

        target = oldest.group_key
        switch_items = [i for i in others if i.group_key == target]
        rest.extend(i for i in others if i.group_key != target)
        for item in adopted:  # older than every row of the group they join
            item.prefix_ids, item.group_key = oldest.prefix_ids, target
            item.pin_spec = oldest.pin_spec
        switch_items = adopted + switch_items
        # Invalidate first — a partial switch (prefix installed, grammar
        # failed) must not leave old-group items matching a half-switched
        # engine.
        self._current_group = None
        try:
            self._install_group(switch_items[0])
            self._current_group = target
        except Exception as exc:  # prefix too long, grammar build
            for item in switch_items:
                item.fail(BackendError(str(exc)))
            return rest
        run_group(switch_items)
        return rest

    def _drain_queue(
        self,
        pending: list[_WorkItem],
        block: bool,
        block_timeout: float | None = None,
    ) -> None:
        """Move queued items into `pending`; a None sentinel sets _stopped.
        `block_timeout` bounds only the FIRST (blocking) get — None waits
        indefinitely."""
        try:
            timeout = block_timeout if block else 0.0
            while True:
                item = (
                    self._queue.get(timeout=timeout) if block else self._queue.get_nowait()
                )
                if item is None:
                    self._stopped.set()
                    return
                pending.append(item)
                block = False
        except queue.Empty:
            pass

    def _try_prewarm(self) -> bool:
        """Compile ONE missing sibling wave geometry while the engine is
        idle (engine.prewarm_wave_siblings). The jit compile blocks this
        thread for seconds — which is exactly why it runs here, after a
        genuine idle grace period, instead of mid-burst when a
        straggler-timing ragged wave would otherwise hit it cold. Requests
        arriving during the compile queue up and are served right after
        (bounded, once per geometry, vs. unbounded mid-burst stall
        risk)."""
        try:
            return self.engine.prewarm_wave_siblings(limit=1) > 0
        except Exception:
            logger.exception("wave prewarm failed")
            return False

    def _prewarm_backlog(self) -> int:
        try:
            return self.engine.wave_prewarm_backlog()
        except AttributeError:  # stub engines
            return 0

    def _run_worker(self) -> None:
        pending: list[_WorkItem] = []
        waves: deque[tuple[Any, list[_WorkItem]]] = deque()
        packs: list[dict] = []  # in-flight packed admissions
        while not self._stopped.is_set():
            block = not pending and not waves and not packs
            if block and self._prewarm_backlog() > 0:
                # Idle with compiles owed: park only for the grace period;
                # if still idle after it, compile ONE sibling geometry,
                # then re-check the queue. Arriving work always wins over
                # starting a prewarm.
                with spans.thread_span("queue_wait", layer="engine"):
                    self._drain_queue(
                        pending, block=True,
                        block_timeout=self.prewarm_idle_delay_s,
                    )
                if self._stopped.is_set():
                    break
                if not pending:
                    with spans.thread_span("prewarm", layer="engine"):
                        self._try_prewarm()
                continue
            if block:
                with spans.thread_span("queue_wait", layer="engine"):
                    self._drain_queue(pending, block=True)
            else:
                self._drain_queue(pending, block=False)
            if self._stopped.is_set() or (
                not pending and not waves and not packs
            ):
                continue
            # Nothing below may kill the engine-owner thread — a dead worker
            # bricks every future request.
            try:
                with spans.thread_span("tick", layer="engine"):
                    pending = self._worker_tick(pending, waves, packs)
            except Exception as exc:  # pragma: no cover - last-resort guard
                logger.exception("engine worker tick failed")
                for _, items in waves:
                    for item in items:
                        item.fail(BackendError(str(exc)))
                waves.clear()
                for pk in packs:
                    for item in pk["items"].values():
                        item.fail(BackendError(str(exc)))
                if packs:
                    # the failed packs' requests still hold _by_slot
                    # entries and KV pages — without an abort they leak
                    # forever (nothing steps an empty packs list) and
                    # free_slots shrinks until nothing admits
                    packs.clear()
                    try:
                        self.engine.abort_all()
                    except Exception:  # pragma: no cover - best effort
                        logger.exception("engine abort after failed tick")
                for item in pending:
                    item.fail(BackendError(str(exc)))
                for ctl in self._held_controls:
                    ctl.fail(BackendError(str(exc)))
                self._held_controls = []
                pending = []
        # Shutdown: fail anything still queued or in flight.
        self._drain_queue(pending, block=False)
        for _, items in waves:
            pending.extend(items)
        for pk in packs:
            pending.extend(pk["items"].values())
        pending.extend(self._held_controls)
        self._held_controls = []
        for item in pending:
            item.fail(BackendError("backend closed"))

    def _drive_packs(self, packs: "list[dict]") -> None:
        """Advance in-flight packed admissions by one decode step and
        resolve any finished decisions (this also harvests decode chunks
        piggybacked during admission — the engine's one sync point).
        Packs admit into FUSED slots: the step routes through the fused
        while_loop runtime when the engine carries one (engine/fused/),
        which early-exits past finished slots and falls back to the
        sparse chunked path on its own when the grammar can't fuse. A
        failed step fails every in-flight packed decision and aborts the
        engine so their slots/pages don't leak."""
        try:
            step_fused = getattr(self.engine, "step_fused", None)
            fins = step_fused() if step_fused is not None else self.engine.step()
        except Exception as exc:
            logger.exception("packed decode step failed")
            for pk in packs:
                for item in pk["items"].values():
                    item.fail(BackendError(str(exc)))
            packs.clear()
            try:
                self.engine.abort_all()
            except Exception:  # pragma: no cover - best-effort cleanup
                logger.exception("engine abort after failed step")
            return
        if not fins:
            return
        now = time.perf_counter()
        with spans.thread_span("resolve", layer="engine"):
            for fin in fins:
                for pk in packs:
                    item = pk["items"].pop(fin.req_id, None)
                    if item is not None:
                        handle = SimpleNamespace(submitted_at=pk["submitted_at"])
                        self._attach_item_spans(item, handle, fin, now)
                        item.resolve(fin.text)
                        break
        packs[:] = [pk for pk in packs if pk["items"]]

    def _worker_tick(
        self,
        pending: list[_WorkItem],
        waves: "deque[tuple[Any, list[_WorkItem]]]",
        packs: "list[dict]",
    ) -> list[_WorkItem]:
        """One submit+harvest cycle; returns items still waiting on a group
        switch."""
        if pending and self.admit_wait_s and not waves:
            # Adaptive coalescing: a burst's leaders enqueue over a few ms;
            # keep extending the window while items are still arriving (up
            # to 5 extensions) so the whole burst lands in ONE wave instead
            # of a wide wave plus straggler waves serialized behind it.
            with spans.thread_span("admit_hold", layer="engine"):
                for _ in range(5):
                    before = len(pending)
                    time.sleep(self.admit_wait_s)  # graftlint: ok[raw-clock] — engine-owner thread paces REAL device admission; virtual-time runs stub the backend above this layer
                    self._drain_queue(pending, block=False)
                    if len(pending) == before or len(pending) >= self.engine.max_slots:
                        break
        pending = self._submit_waves(pending, waves, packs)
        if packs:
            # Packed admissions decode via the paged path: advance them
            # (and harvest piggybacked emissions) every tick so their
            # decisions resolve while waves pipeline alongside.
            self._drive_packs(packs)
        if waves:
            handle, items = waves[0]
            # While the oldest wave executes, keep feeding the pipeline:
            # stragglers arriving now become the NEXT wave, overlapping
            # with this one on device instead of waiting behind a blocking
            # sync. The wait blocks on the queue (2ms granularity for the
            # is_ready re-check) rather than busy-polling. The poll is
            # DEADLINE-BOUNDED by the wave-service EMA: is_ready() may
            # flip only when the whole enqueued chain drains, so past the
            # point where this wave should be done we stop polling and
            # harvest BLOCKINGLY — device_get returns at
            # the wave's true completion, which is what its leaders (and
            # all their parked followers) are waiting on. The 0.5 factor
            # biases the deadline LOW on purpose: an early blocking
            # harvest returns at (and therefore MEASURES) the true
            # completion time, keeping the EMA accurate — a high deadline
            # would record its own lateness into the EMA and never
            # converge back down (the stable band is ema in [true, 2x
            # true], so the poll window is 0.5-1.0x the true service).
            # Anchored to when the device could have STARTED this wave
            # (its submit, or the previous harvest) — anchoring to submit
            # alone would pre-expire the deadline for every wave behind
            # the first and degenerate the pipeline to serial harvests.
            geo = getattr(handle, "geo_key", None)
            ema = self._wave_ema.get(geo, self._wave_ema_default)
            deadline = (
                max(handle.submitted_at, self._last_harvest_t) + 0.5 * ema
            )
            if packs:
                # in-flight packed decodes must not starve behind the
                # straggler poll — harvest this wave blockingly and get
                # back to stepping them
                deadline = 0.0
            # ONE span around the whole poll, never one per iteration;
            # waves submitted from inside it nest as engine.submit_wave
            with spans.thread_span(
                "harvest_poll", layer="engine",
                wave=getattr(handle, "seq", 0),
            ):
                while (
                    not handle.is_ready()
                    and not self._stopped.is_set()
                    and time.perf_counter() < deadline
                ):
                    try:
                        got = self._queue.get(timeout=0.002)
                    except queue.Empty:
                        if pending:
                            # held ragged tails re-check their hold deadline
                            # even with no new arrivals (run_group)
                            pending = self._submit_waves(pending, waves, packs)
                        continue
                    if got is None:
                        self._stopped.set()
                        break
                    pending.append(got)
                    self._drain_queue(pending, block=False)
                    pending = self._submit_waves(pending, waves, packs)
            prof = getattr(self.engine, "profiler", None)
            if prof is not None and handle.is_ready():
                # ready edge observed by the poll (or already ready when
                # the poll deadline expired): the profiler's device-compute
                # estimate ends here, not at the blocking device_get
                prof.note_ready(handle)
            waves.popleft()
            try:
                fins = self.engine.harvest_wave(handle)
            except Exception as exc:
                logger.exception("wave harvest failed")
                for item in items:
                    item.fail(BackendError(str(exc)))
            else:
                now = time.perf_counter()
                # Marginal service time of THIS wave: from when the device
                # could have started it (its submit, or the previous
                # wave's completion) to its completion. Feeds the poll
                # deadline above. Waves whose geometry jit-compiled at
                # dispatch are EXCLUDED — their wall time is compile +
                # execution and would poison the estimate (a poisoned-high
                # EMA delays every subsequent harvest past true
                # completion until it decays). The remaining update is
                # asymmetric: fast down; up capped RELATIVE (4x) so
                # multi-second waves at 8B+ scale still converge in a few
                # steps while any residual outlier moves it at most ~30%.
                service = max(now - max(handle.submitted_at, self._last_harvest_t), 0.02)
                self._last_harvest_t = now
                if not getattr(handle, "cold_compile", False):
                    ema = self._wave_ema.get(geo, self._wave_ema_default)
                    if service < ema:
                        ema = 0.5 * ema + 0.5 * service
                    else:
                        ema = 0.9 * ema + 0.1 * min(service, 4.0 * ema)
                    self._wave_ema[geo] = ema
                with spans.thread_span(
                    "resolve", layer="engine", wave=getattr(handle, "seq", 0),
                ):
                    for fin, item in zip(fins, items):
                        self._attach_item_spans(item, handle, fin, now)
                        item.resolve(fin.text)
        if self._held_controls and not waves and not packs:
            # Wave barrier reached (everything in flight harvested above —
            # waves and packed admissions — admissions held since the
            # control arrived): run the quiesced actions on this — the
            # engine-owner — thread. Held work in `pending` resumes on the
            # next tick.
            controls, self._held_controls = self._held_controls, []
            for ctl in controls:
                try:
                    with spans.thread_span("control", layer="engine"):
                        result = ctl.fn()
                except Exception as exc:
                    logger.exception("quiesced control action failed")
                    ctl.fail(exc)
                else:
                    pause_s = time.perf_counter() - ctl.enqueued_at
                    self.swap_stats["quiesce_runs"] += 1
                    self.swap_stats["last_pause_s"] = pause_s
                    self.swap_stats["total_pause_s"] += pause_s
                    if not ctl.future.done():
                        ctl.future.set_result((result, pause_s))
            # A control may have invalidated engine state the group key
            # stands for (a weight swap clears the prefix KV): drop the
            # group so the next wave REINSTALLS prefix + grammar instead
            # of matching the old key and decoding against an empty
            # prefix. Costs one prefix prefill per quiesce — correctness
            # over a cache hit. Pinned snapshot prefixes went stale with
            # the same swap (engine.prefix_epoch bump): tidy the manager
            # so the next group install re-pins under the new weights.
            self._current_group = None
            if self._pin_manager is not None:
                self._pin_manager.invalidate_stale()
        return pending

    @staticmethod
    def _attach_item_spans(item: _WorkItem, handle, fin, now: float) -> None:
        """Attach this item's engine-side spans to its decision trace at
        harvest (the first moment all the numbers exist):

        - admission_wait: enqueue -> wave dispatch (queue + coalescing
          window + group-switch fairness holds);
        - wave: submit -> harvest on the host clock, MEASURED, with the
          wave's number (`wave`, WaveHandle.seq), its rows and model
          calls, and this item's suffix and served token counts. The
          number is also on admission_wait and in the trace's meta: it
          names the `jit_wave` run of a device trace that served this
          decision (the k-th run is the k-th engine.submit_wave). Other
          waves are in flight beside it, so this is a latency, not the
          device time the decision cost. Waves only: a packed
          decision has no wave;
        - prefill / decode: the same interval apportioned by token
          counts (the wave is ONE fused device program — the split is the
          same token-apportioned estimate sim/arena uses, flagged
          `apportioned`), carrying suffix/emission token counts.

        Runs on the engine-owner thread; Trace.add_span is lock-guarded
        for exactly this producer."""
        cap = item.trace
        if cap is None:
            return
        try:
            trace, ctx = cap
            # perf_counter -> wall clock via this item's own enqueue pair
            wall_offset = item.enqueued_wall - item.enqueued_at
            submitted = getattr(handle, "submitted_at", item.enqueued_at)
            admission_ms = max(submitted - item.enqueued_at, 0.0) * 1000.0
            seq = getattr(handle, "seq", None)
            wave_attr = {} if seq is None else {"wave": seq}
            # publish=False + one flush: on the late-harvest path (root
            # already recorded) each publishing add_span would pay a full
            # trace reserialization — batch them, re-publish once
            trace.add_span(
                "admission_wait", start_unix=item.enqueued_wall,
                dur_ms=admission_ms, parent_id=ctx.span_id, publish=False,
                **wave_attr,
            )
            wave_ms = max(now - submitted, 0.0) * 1000.0
            pf = len(item.suffix_ids or ())
            dc = len(fin.token_ids)
            total = pf + dc
            prefill_ms = wave_ms * pf / total if total else 0.0
            submit_wall = submitted + wall_offset
            if seq is not None:
                trace.set_meta(wave=seq)
                trace.add_span(
                    "wave", start_unix=submit_wall, dur_ms=wave_ms,
                    parent_id=ctx.span_id, publish=False, wave=seq,
                    rows=handle.n, suffix_tokens=pf, served_tokens=dc,
                    model_calls=handle.model_calls,
                )
            trace.add_span(
                "prefill", start_unix=submit_wall, dur_ms=prefill_ms,
                parent_id=ctx.span_id, tokens=pf, apportioned=True,
                publish=False,
            )
            trace.add_span(
                "decode", start_unix=submit_wall + prefill_ms / 1000.0,
                dur_ms=wave_ms - prefill_ms, parent_id=ctx.span_id,
                tokens=dc, apportioned=True, publish=False,
            )
            trace.flush()
        except Exception:  # tracing must never fail a decision
            logger.exception("failed to attach engine spans")

    def run_quiesced(self, fn, timeout_s: float | None = None):
        """Run `fn()` on the engine-owner thread at a wave barrier.

        From the moment the control enqueues, the worker holds ALL new
        admissions, drains every in-flight wave, runs `fn`, and only then
        resumes — the quiesce discipline a hot weight swap needs (no wave
        may straddle a params swap, no request is failed or dropped:
        held work simply waits out the pause). Decode service for queued
        requests resumes on the very next tick.

        Thread-safe (any caller thread); blocks until done. Returns
        (fn result, pause_s) where pause_s is the admission-held wall
        time — THE swap-pause metric. Raises what fn raises."""
        if self._stopped.is_set():
            raise BackendError("backend closed")
        ctl = _ControlItem(fn)
        self._queue.put(ctl)
        try:
            return ctl.future.result(timeout=timeout_s)
        except FuturesTimeout as exc:
            raise BackendError(
                f"quiesced action not executed within {timeout_s}s"
            ) from exc

    def close(self) -> None:
        self._stopped.set()
        self._queue.put(None)
        self._worker.join(timeout=5)
        prof = getattr(self.engine, "profiler", None)
        if prof is not None:
            # flush half-open wave fences AFTER the worker joined: in-flight
            # waves were failed upstream and will never harvest, and a
            # leaked fence map is exactly the shutdown residue the
            # lifecycle tests pin (tests/test_profiler.py)
            prof.close()

    def attach_kvplane(
        self,
        store,
        *,
        replica: str = "r0",
        transport: str = "host",
        wait_checks: int = 2,
    ) -> None:
        """Join this backend to a fleet-shared prefix-KV plane
        (fleet/kvplane/): snapshot pins route through a KVPlaneClient —
        adopt a peer's published pages when available, else win the fill
        election, prefill once, and publish for the fleet. Requires a
        pinning engine (no-op otherwise, matching the pin manager's own
        gating on test doubles)."""
        if self._pin_manager is None:
            return
        from k8s_llm_scheduler_tpu.fleet.kvplane import KVPlaneClient

        client = KVPlaneClient(
            store,
            self.engine,
            replica=replica,
            transport=transport,
            wait_checks=wait_checks,
        )
        self._kvplane = client
        self._pin_manager.kvplane = client

    def get_stats(self) -> dict[str, Any]:
        out = self.engine.get_stats()
        if self.swap_stats["quiesce_runs"]:
            out["swap"] = dict(self.swap_stats)
        if self.pool_role != "mixed":
            out["pool_role"] = self.pool_role
            out["role_refusals"] = self.role_refusals
        if self._delta is not None:
            out["delta"] = self._delta.stats()
        if self._pin_manager is not None:
            pin_stats = self._pin_manager.stats()
            if pin_stats["pins"]:
                out["pins"] = pin_stats
        if self._kvplane is not None:
            out["kvplane"] = self._kvplane.stats()
        # a restart as the operator sees it: how long set-up took, and what
        # it traced, lowered, loaded or compiled (totals; chip_smoke.py
        # prints the per-program table)
        out["setup"] = {
            "build_s": self.setup.get("setup_build", 0.0),
            "params_s": self.setup.get("setup_params", 0.0),
            "tokenizer_s": self.setup.get("setup_tokenizer", 0.0),
            "params_wait_s": self.setup.get("setup_wait", 0.0),
            **COMPILE_LOG.books(),
        }
        # THE admission-efficiency headline (sublinearity in node count is
        # measured on this): prefill tokens actually computed per finished
        # decision — prefix prefills count only NON-REUSED tokens, so
        # delta encoding + pinning drive this toward O(changed).
        completed = out.get("completed", 0)
        if completed:
            out["prefill_tokens_per_decision"] = round(
                out.get("prefill_tokens", 0) / completed, 2
            )
        return out


def _attach_spec(
    engine: InferenceEngine,
    *,
    arm: str,
    draft_model: str,
    draft_checkpoint: str | None,
    k: int,
    disable_threshold: float,
    rng_seed: int,
) -> None:
    """Build the speculative arm and attach a SpeculativeDecoder.

    `arm="draft"`: a second (small) model — the draft serves the SAME
    tokenizer as the target (a distilled draft — train/distill.py —
    trains on exactly that vocab); a random-init draft config narrower
    than the tokenizer is widened so every legal token is proposable,
    a checkpoint must already match (SpeculativeDecoder validates).
    `arm="hidden"`: the draft-free hidden-transfer head (spec/hidden.py)
    — `draft_checkpoint` then names a train/hidden.py head checkpoint
    (random-init without one; correctness never depends on training,
    only acceptance does)."""
    from k8s_llm_scheduler_tpu.spec import SpeculativeDecoder

    if arm not in ("draft", "hidden"):
        # A typo must not silently serve the wrong pipeline (the draft
        # branch would otherwise swallow any unknown value).
        raise ValueError(f"unknown llm.spec_arm {arm!r}")
    if arm == "hidden":
        hidden_head = None
        if draft_checkpoint:
            from k8s_llm_scheduler_tpu.train.hidden import (
                restore_hidden_transfer,
            )

            hidden_head = restore_hidden_transfer(
                Path(draft_checkpoint), engine.cfg, k
            )
        engine.attach_spec(
            SpeculativeDecoder(
                engine, arm="hidden", hidden_head=hidden_head,
                hidden_seed=rng_seed + 1,
                k=k, disable_threshold=disable_threshold,
            )
        )
        logger.info(
            "speculative decoding attached: arm=hidden k=%d disable<%.2f%s",
            k, disable_threshold,
            " (checkpoint)" if draft_checkpoint else " (random-init)",
        )
        return
    from k8s_llm_scheduler_tpu.spec.draft import build_random_draft

    draft_cfg = get_config(draft_model)
    if draft_checkpoint:
        from k8s_llm_scheduler_tpu.models.loader import restore_checkpoint

        draft_params = restore_checkpoint(Path(draft_checkpoint), draft_cfg, None)
    else:
        draft_params, draft_cfg = build_random_draft(
            draft_cfg, engine.tokenizer.vocab_size, rng_seed + 1
        )
    engine.attach_spec(
        SpeculativeDecoder(
            engine, draft_params, draft_cfg,
            k=k, disable_threshold=disable_threshold,
        )
    )
    logger.info(
        "speculative decoding attached: draft=%s k=%d disable<%.2f%s",
        draft_cfg.name, k, disable_threshold,
        " (checkpoint)" if draft_checkpoint else " (random-init)",
    )


def _pin_quantized(params, cfg, mesh):
    """Re-pin an int8 `{"q", "scale"}` tree to the serving plane's
    quantization-aware specs (engine/sharded.serving_param_specs).

    quantize_params runs AFTER shard_params on the tp path, and GSPMD
    leaves the reduction-produced scale tensors wherever its solver put
    them — layout-compatible but unspecified. Serving needs the layout
    pinned: hot-swap restores and param donation both compare against
    the booted placement, and an unpinned scale would make tp swaps
    reshard on every rollout."""
    from k8s_llm_scheduler_tpu.engine.sharded import serving_param_specs

    return shard_params(
        params, mesh, serving_param_specs(cfg, quantized=True)
    )


def _init_params(rng_seed: int, cfg, mesh=None):
    """Random-init the bf16 tree in ONE jitted program, for every layout.
    With a mesh the outputs are born on it (param_specs match the
    unquantized tree): each device draws only its own 1/N of every weight
    (threefry is partitionable — GSPMD shards the draw itself), so a model
    that needs the mesh — 8B bf16 is 16 GB — never exists whole on device
    0. One program means one set of weights: tp=1 and tp=N start from
    bit-identical trees, which is what lets a greedy token digest be
    compared across layouts."""
    shardings = None if mesh is None else named_shardings(mesh, param_specs(cfg))
    return jax.jit(
        functools.partial(family(cfg).init_params, cfg=cfg),
        out_shardings=shardings,
    )(jax.random.PRNGKey(rng_seed))


def _refuse_unserved(cfg, *, multi, quantize, checkpoint_path, spec_enabled) -> None:
    """What the families other than the dense one (models/llama.py) do not
    bring refuses HERE, at build time, naming the model and the path —
    never inside a trace: what would otherwise fail before the engine
    exists. (InferenceEngine refuses a tp mesh and
    ragged decode in its constructor, and the paged entry points at the
    call: _require_paged.)"""
    if isinstance(cfg, LlamaConfig):
        return
    unsharded = getattr(family(cfg), "UNSHARDED", (
        "a per-sequence state has no sharding rule"
        if family(cfg).state_shapes(cfg)
        else "a latent cache has no head axis to shard"
    ))
    asked = {
        f"llm.mesh with tp > 1 ({unsharded}; "
        "experts over chips and their exchange: parallel/sharding.py, "
        "engine/sharded/)": multi,
        f"llm.quantization {quantize!r} (int8 expert weights: "
        f"models/quant.py)": quantize is not None,
        "llm.checkpoint_path (this family's checkpoint names: "
        "models/loader.py)": bool(checkpoint_path),
        "llm.spec_enabled (speculative decoding, spec/, runs the paged "
        "pool; decision waves never speculate)": spec_enabled,
    }
    for path, on in asked.items():
        if on:
            raise ValueError(f"{cfg.name}: {path} is not served")


def _require_named_cpu() -> None:
    """CPU only when asked for by name. A cpu default backend that
    `JAX_PLATFORMS` did not put first is JAX having found no accelerator
    and carried on; serving from it would pass for a working deployment at
    a hundredth of the speed, so the one door every entry point uses
    refuses it. (The chip machines set `tpu,cpu`: cpu is listed there as
    the host platform, not as the one to serve from.)"""
    if jax.default_backend() != "cpu":
        return
    asked = jax.config.jax_platforms or ""
    if asked.split(",")[0] == "cpu":
        return
    raise RuntimeError(
        f"JAX came up on the cpu backend but JAX_PLATFORMS={asked!r} did "
        f"not ask for it: no accelerator was found (devices: "
        f"{jax.devices()}). Set JAX_PLATFORMS=cpu to serve from the CPU on "
        f"purpose."
    )


def build_local_backend(
    model: str = "tiny",
    mesh_axes: dict[str, int] | None = None,
    *,
    cfg: LlamaConfig | None = None,
    temperature: float = 0.3,
    max_slots: int = 8,
    num_pages: int = 512,
    page_size: int = 64,
    max_pages_per_seq: int | None = None,
    prefill_buckets: tuple[int, ...] = (128, 256, 512, 1024, 2048, 4096, 8192),
    chunk_steps: int = 16,
    prefix_chunk: int = 2048,
    paged_attn: str = "gather",
    prefix_attn_impl: str | None = None,
    decode_matmul: str = "dense",
    quantize: str | None = None,
    max_new_tokens: int = 200,
    constrained: bool = True,
    rng_seed: int = 0,
    checkpoint_path: str | None = None,
    tokenizer_path: str | None = None,
    tokenizer_name: str = "byte",
    devices: Sequence[Any] | None = None,
    request_timeout_s: float = 60.0,
    group_switch_after_s: float = 0.25,
    partial_hold_s: float = 0.03,
    prewarm_idle_delay_s: float = 0.5,
    compile_cache_dir: str | None = "auto",
    answer_style: str = "direct",
    max_reason_tokens: int = 320,
    spec_enabled: bool = False,
    spec_arm: str = "draft",
    spec_draft_model: str = "tiny",
    spec_draft_checkpoint: str | None = None,
    spec_k: int = 4,
    spec_disable_threshold: float = 0.3,
    packed_admission: bool = True,
    admission_chunk_tokens: int = 256,
    delta_prompts: bool = False,
    repin_fraction: float = 0.25,
    max_pins: int = 4,
    fused_decode: bool = True,
    top_k: int = 0,
) -> LocalLLMBackend:
    """Construct the full local stack: params (from an HF safetensors or
    orbax checkpoint when checkpoint_path is set, random-init otherwise —
    models/loader.py), mesh sharding, engine, backend.

    `devices` overrides the mesh's device pool (default: jax.devices()) —
    used by the driver dryrun to target the virtual CPU mesh explicitly.
    `compile_cache_dir` places JAX's persistent compilation cache so engine
    program geometries compiled by ANY previous process load instead of
    re-jitting: "auto" = `<checkout>/.xla_cache`, None disables, and a set
    `JAX_COMPILATION_CACHE_DIR` wins over both — then nothing is set in
    code (utils/compile_cache.py).

    Raises when JAX came up on the cpu backend without `JAX_PLATFORMS`
    naming it: that is a missing accelerator, not a request for CPU."""
    COMPILE_LOG.install()  # before the first jit: set-up's programs are booked
    setup: dict[str, float] = {}
    with spans.thread_span("setup_build", layer="engine", sink=setup):
        _require_named_cpu()
        enable_persistent_compile_cache(compile_cache_dir)
        cfg = cfg or get_config(model)
        builtin_tokenizer = None
        if tokenizer_path is None and not (
            checkpoint_path
            and tokenizer_name == "byte"
            and (Path(checkpoint_path) / "tokenizer.json").exists()
        ):
            # Builtin tokenizer: the shared rule in engine/tokenizer.py may
            # WIDEN cfg.vocab_size (numeric NUM rows live above the byte
            # base) — this must happen before params are built; train/
            # distill.py calls the same helper, so checkpoints round-trip.
            from k8s_llm_scheduler_tpu.engine.tokenizer import (
                build_builtin_tokenizer,
            )

            builtin_tokenizer, cfg = build_builtin_tokenizer(tokenizer_name, cfg)
        mesh = mesh_from_config(mesh_axes, devices=devices)
        multi = mesh.devices.size > 1
        # Serving shards over tp only: params are tp-sharded (Megatron specs)
        # and the engine's wave batch is replicated, so a dp/sp/... axis > 1
        # would replicate weights N times and waste every non-tp device.
        # Reject loudly instead of silently burning chips (VERDICT r2 weak #3).
        bad_axes = {
            ax: n for ax, n in mesh.shape.items() if ax != "tp" and n > 1
        }
        if bad_axes:
            raise ValueError(
                f"serving mesh supports only a tp axis; got {bad_axes} — "
                f"use llm.mesh {{tp: N}} (dp batch sharding is a training-path "
                f"concept; the engine's continuous batching already fills the "
                f"chip with one replica)"
            )
        _refuse_unserved(
            cfg, multi=multi, quantize=quantize, checkpoint_path=checkpoint_path,
            spec_enabled=spec_enabled,
        )
        if multi:
            validate_specs_divisibility(cfg, mesh)
        if quantize is not None and quantize != "int8":
            raise ValueError(f"unknown quantization {quantize!r} (only 'int8')")
        # from the weights' dispatch until they are resident; the wait is
        # the block's last line, so the host work below still overlaps it
        with spans.thread_span("setup_params", layer="engine", sink=setup):
            if checkpoint_path:
                from k8s_llm_scheduler_tpu.models.loader import (
                    load_hf_checkpoint,
                    restore_checkpoint,
                )

                ckpt = Path(checkpoint_path)
                if list(ckpt.glob("*.safetensors")):
                    # quantizes per stacked parameter as it completes — the bf16
                    # form of at most one parameter is ever resident
                    params = load_hf_checkpoint(
                        ckpt, cfg, mesh if multi else None, quantize=quantize
                    )
                else:
                    params = restore_checkpoint(ckpt, cfg, mesh if multi else None)
                    if quantize is not None:
                        from k8s_llm_scheduler_tpu.models.quant import quantize_params

                        params = quantize_params(params)
                        if multi:
                            params = _pin_quantized(params, cfg, mesh)
            elif multi:
                params = _init_params(rng_seed, cfg, mesh)
                if quantize is not None:
                    from k8s_llm_scheduler_tpu.models.quant import quantize_params

                    params = quantize_params(params)
                    params = _pin_quantized(params, cfg, mesh)
            elif quantize == "int8":
                # single device: init + quantize HOST-SIDE, ship only int8 — even
                # per-weight bf16 device transients overflow a 16 GB chip at 8B
                from k8s_llm_scheduler_tpu.models.quant import init_params_int8_host

                params = init_params_int8_host(rng_seed, cfg)
            else:
                params = _init_params(rng_seed, cfg)
            with spans.thread_span("setup_tokenizer", layer="engine", sink=setup):
                if builtin_tokenizer is not None:
                    tokenizer = builtin_tokenizer
                else:
                    # a HF tokenizer dir was given, or the checkpoint ships its
                    # own (auto-adopted only when no builtin was explicitly
                    # selected — a numeric-distilled checkpoint must keep the
                    # vocab it trained on)
                    from k8s_llm_scheduler_tpu.engine.tokenizer import (
                        HFTokenizerAdapter,
                    )

                    tokenizer = HFTokenizerAdapter(tokenizer_path or checkpoint_path)
            if max_pages_per_seq is None:
                # Own pages hold only the per-pod suffix + generated tokens (the
                # shared cluster-state prefix lives in the dense prefix buffer), so
                # the page-table width — which sets the decode gather size — stays
                # tight: the largest suffix we expect (1024 tokens covers a pod spec
                # with heavy selectors/tolerations; LocalLLMBackend fails bigger ones
                # individually via max_suffix_tokens) + decode budget.
                max_pages_per_seq = -(-(1024 + max_new_tokens + chunk_steps) // page_size)
            engine = InferenceEngine(
                params, cfg, tokenizer,
                num_pages=num_pages, page_size=page_size, max_slots=max_slots,
                max_pages_per_seq=max_pages_per_seq,
                prefill_buckets=prefill_buckets, chunk_steps=chunk_steps,
                prefix_chunk=prefix_chunk, paged_attn=paged_attn,
                temperature=temperature,
                # On a tp mesh the engine wraps the Pallas kernels in shard_map
                # over the kv-head axis (ops/pallas_prefix_attention.py shmap
                # wrappers), so the sharded serving path keeps flash attention.
                prefix_attn_impl=prefix_attn_impl,
                decode_matmul=decode_matmul,
                mesh=mesh if multi else None,
                admission_chunk_tokens=admission_chunk_tokens,
                fused_decode=fused_decode,
                top_k=top_k,
            )
            if spec_enabled:
                if multi:
                    # The spec programs carry no sharding annotations yet; on a tp
                    # mesh they would gather the sharded caches through GSPMD's
                    # worst guesses. Plain decode is the honest multi-device path.
                    logger.warning(
                        "spec_enabled is single-device; tp mesh keeps plain decode"
                    )
                else:
                    _attach_spec(
                        engine,
                        arm=spec_arm,
                        draft_model=spec_draft_model,
                        draft_checkpoint=spec_draft_checkpoint,
                        k=spec_k,
                        disable_threshold=spec_disable_threshold,
                        rng_seed=rng_seed,
                    )
            backend = LocalLLMBackend(
                engine, tokenizer, max_new_tokens=max_new_tokens, constrained=constrained,
                request_timeout_s=request_timeout_s,
                group_switch_after_s=group_switch_after_s,
                partial_hold_s=partial_hold_s,
                prewarm_idle_delay_s=prewarm_idle_delay_s,
                answer_style=answer_style,
                max_reason_tokens=max_reason_tokens,
                packed_admission=packed_admission,
                delta_prompts=delta_prompts,
                repin_fraction=repin_fraction,
                max_pins=max_pins,
            )
            # what the weights' draw or load still costs once the host work
            # above no longer hides it
            with spans.thread_span("setup_wait", layer="engine", sink=setup):
                jax.block_until_ready(backend.engine.params)
    backend.setup = setup
    return backend
