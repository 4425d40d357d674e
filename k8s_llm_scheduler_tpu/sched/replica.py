"""Cross-host decision serving: replica workers + coordinator fan-out.

SCALING.md's multi-host serving layout is replica-per-host (weights
replicated over hosts, tp within each host's ICI domain) — but through
round 3 only the coordinator actually SERVED: workers had a backend and no
way to receive work. This module is the missing transport:

- `ReplicaServer`: runs on a worker host next to its LocalLLMBackend;
  accepts length-delimited JSON requests over TCP and answers each with
  the backend's SchedulingDecision. Connections are handled on threads and
  requests WITHIN a connection are executed concurrently — the worker's
  engine sees the same concurrency a local DecisionClient would produce,
  so its wave batching still coalesces a burst's leaders.
- `ReplicaClient`: a MULTIPLEXING client (one socket, id-tagged frames, a
  reader thread resolving per-request futures). Concurrent coordinator
  requests interleave on the wire instead of serializing, which is what
  keeps the remote engine's waves full.
- `FanoutBackend`: the coordinator-side DecisionBackend that round-robins
  decisions across [local backend, replica clients...]. It sits BELOW
  DecisionClient, so the cache / single-flight / breaker / fallback stack
  is untouched: only leader decisions (cache misses) ever reach a replica.

The control plane stays coordinator-only (watch/bind; parallel/
distributed.is_coordinator) — what fans out is pure model compute, the
part that scales with replica count. K8s traffic does not multiply.

Transport is dependency-free (socket + json + threading): 4-byte
big-endian length prefix, UTF-8 JSON payload. Request:
{"id": n, "pod": {...}, "nodes": [...]}; response: {"id": n,
"decision": {...}} | {"id": n, "error": str, "kind":
"infeasible"|"backend"}.

Validated end to end (two real processes, decisions on both) by
tools/dryrun_multihost.py; protocol/fan-out unit tests in
tests/test_replica.py.
"""

from __future__ import annotations

import dataclasses
import inspect
import itertools
import json
import logging
import math
import random
import socket
import struct
import threading
import time
from collections import deque
from collections.abc import Sequence
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Any, Callable

from k8s_llm_scheduler_tpu.engine.backend import (
    BackendError,
    DecisionBackend,
    NoFeasibleNodeError,
)
from k8s_llm_scheduler_tpu.observability import spans
from k8s_llm_scheduler_tpu.sched import deadline as deadline_mod
from k8s_llm_scheduler_tpu.sched.deadline import (
    DeadlineBudget,
    DeadlineExceededError,
)
from k8s_llm_scheduler_tpu.types import (
    DecisionSource,
    NodeMetrics,
    PodSpec,
    SchedulingDecision,
)

logger = logging.getLogger(__name__)

_LEN = struct.Struct(">I")
MAX_FRAME = 64 << 20  # sanity bound; a 10k-pod snapshot is ~3 MB of JSON


# ------------------------------------------------------------------ frames
def _encode_frame(obj: dict) -> tuple[bytes, bytes]:
    """(length header, JSON payload) — encoded once; the payload bytes are
    handed to the kernel as a memoryview and never copied again."""
    payload = json.dumps(obj).encode("utf-8")
    return _LEN.pack(len(payload)), payload


def _send_frames(
    sock: socket.socket, frames: "Sequence[tuple[bytes, bytes]]"
) -> None:
    """Zero-copy vectored frame write: every frame's (header, payload)
    pair joins ONE scatter-gather `sendmsg` iovec — no header+payload
    concatenation (the old path copied every payload a second time), and
    a BATCH of frames costs one syscall instead of one per frame (the
    client's outbox coalescing rides on exactly this). Partial sends
    advance through the iovec with memoryview slices; sockets without
    sendmsg fall back to per-buffer sendall."""
    bufs: list[memoryview] = []
    for header, payload in frames:
        bufs.append(memoryview(header))
        bufs.append(memoryview(payload))
    sendmsg = getattr(sock, "sendmsg", None)
    if sendmsg is None:  # pragma: no cover - platform without sendmsg
        for b in bufs:
            sock.sendall(b)
        return
    # The kernel caps one sendmsg at IOV_MAX iovecs (1024 on Linux): a
    # large drained outbox batch must chunk or a HEALTHY socket raises
    # EMSGSIZE and the flush wrongly fails every batchmate.
    iov_max = min(getattr(socket, "IOV_MAX", 1024), 1024)
    while bufs:
        n = sendmsg(bufs[:iov_max])
        while n:
            if n >= len(bufs[0]):
                n -= len(bufs[0])
                bufs.pop(0)
            else:
                bufs[0] = bufs[0][n:]
                n = 0


def _send_frame(sock: socket.socket, obj: dict) -> None:
    _send_frames(sock, [_encode_frame(obj)])


def _set_nodelay(sock: socket.socket) -> None:
    """Disable Nagle: decision frames are small and latency-critical —
    leaving coalescing to the kernel adds up to one delayed-ACK round
    trip (~40ms) per frame, a direct dispatch_rtt_ms term. Batching is
    done deliberately at the framing layer (_send_frames) instead."""
    try:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    except OSError:
        pass  # best-effort (some socketpairs/platforms refuse)


def _recv_exact(sock: socket.socket, n: int) -> bytes | None:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf.extend(chunk)
    return bytes(buf)


def _recv_frame(sock: socket.socket) -> dict | None:
    header = _recv_exact(sock, _LEN.size)
    if header is None:
        return None
    (length,) = _LEN.unpack(header)
    if length > MAX_FRAME:
        raise BackendError(f"replica frame of {length} bytes exceeds bound")
    payload = _recv_exact(sock, length)
    if payload is None:
        return None
    return json.loads(payload.decode("utf-8"))


# ------------------------------------------------------------- serialization
def pod_to_wire(pod: PodSpec) -> dict:
    return dataclasses.asdict(pod)


def pod_from_wire(d: dict) -> PodSpec:
    d = dict(d)
    d["tolerations"] = tuple(d.get("tolerations") or ())
    return PodSpec(**d)


def node_to_wire(node: NodeMetrics) -> dict:
    return dataclasses.asdict(node)


def node_from_wire(d: dict) -> NodeMetrics:
    d = dict(d)
    d["taints"] = tuple(d.get("taints") or ())
    return NodeMetrics(**d)


def decision_to_wire(dec: SchedulingDecision) -> dict:
    d = dataclasses.asdict(dec)
    d["source"] = dec.source.value
    return d


def decision_from_wire(d: dict) -> SchedulingDecision:
    d = dict(d)
    d["source"] = DecisionSource(d["source"])
    return SchedulingDecision(**d)


# ------------------------------------------------------------------- server
class ReplicaServer:
    """Serve a DecisionBackend over TCP on a worker host.

    One accept thread; one reader thread per connection; requests within a
    connection run CONCURRENTLY on a bounded executor (`max_inflight`) —
    the engine's wave batching depends on seeing the burst's leaders
    together, and the engine-owner thread in LocalLLMBackend already
    serializes device access safely, but an unbounded thread-per-request
    design let any client spawn unbounded threads.

    Trust model: the protocol is unauthenticated JSON-RPC that drives model
    compute — it must only be reachable from the coordinator. The default
    bind is loopback; multi-host deployments set
    `distributed.replica_bind_host` to the worker's pod/host IP (or
    explicitly to "0.0.0.0" on a trusted network).
    """

    def __init__(self, backend: DecisionBackend, host: str = "localhost",
                 port: int = 9901, max_inflight: int = 64,
                 max_connections: int = 16,
                 swap_fn: Callable[[int], dict] | None = None,
                 pool_role: str = "mixed",
                 telemetry_fn: Callable[[dict], dict] | None = None) -> None:
        from concurrent.futures import ThreadPoolExecutor

        from k8s_llm_scheduler_tpu.fleet.pools import POOL_ROLES

        self.backend = backend
        # Disaggregated-pool role (fleet/pools.py): a "decode" worker
        # refuses admission (work="prefill") frames so a misrouting fleet
        # frontend fails loudly instead of silently evicting decode
        # throughput. "mixed" (default) accepts everything — single-pool
        # deployments are unchanged.
        if pool_role not in POOL_ROLES:
            raise ValueError(
                f"pool_role {pool_role!r} not in {POOL_ROLES}"
            )
        self.pool_role = pool_role
        # capability probes, ONCE (not per request): does the backend
        # understand the work tag / the prepacked batch surface?
        try:
            self._backend_accepts_work = "work" in inspect.signature(
                backend.get_scheduling_decision
            ).parameters
        except (TypeError, ValueError):
            self._backend_accepts_work = False
        self._backend_batch = getattr(
            backend, "get_scheduling_decisions_batch", None
        )
        # Optional rollout hook: `swap_fn(version) -> dict` hot-swaps THIS
        # worker's backend to a registry version (rollout/hotswap.py
        # HotSwapper.swap_to over a registry the worker can read). The
        # coordinator's canary controller staggers these one replica at a
        # time (rollout/canary.staggered_swap) so the fanout always keeps
        # a serving majority. None = the op answers ok=False.
        self.swap_fn = swap_fn
        # Fleet telemetry hook (observability/fleetview.py): the
        # `telemetry_pull` op ships this worker's stats tree, a
        # since-cursor flight-recorder slice, and its sampler ring to the
        # aggregator. `telemetry_fn(request) -> payload` overrides the
        # default (backend stats + the process-global flight recorder) for
        # deployments that wire a scheduler-level stats provider.
        self.telemetry_fn = telemetry_fn
        self.max_inflight = max_inflight
        self.max_connections = max_connections
        self._pool = ThreadPoolExecutor(
            max_workers=max_inflight, thread_name_prefix="replica-req"
        )
        # in-flight = queued + executing: the executor's own queue is
        # unbounded, so admission is gated here — excess requests get an
        # immediate "overloaded" error response instead of queueing
        # unbounded memory
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        self._sock = socket.create_server((host, port))
        self.port = self._sock.getsockname()[1]  # resolved (port=0 allowed)
        self._stop = threading.Event()
        self.served = 0
        self._served_lock = threading.Lock()
        # live per-connection sockets: close() must shut these down too —
        # closing only the listener left connection threads serving
        # requests after "shutdown" (a stopped worker kept answering)
        self._conns: set[socket.socket] = set()
        self._conns_lock = threading.Lock()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True, name="replica-accept"
        )
        self._accept_thread.start()

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, addr = self._sock.accept()
            except OSError:
                return  # socket closed by stop()
            logger.info("replica: accepted connection from %s:%s", *addr[:2])
            threading.Thread(
                target=self._serve_conn, args=(conn, addr), daemon=True,
                name=f"replica-conn-{addr[1]}",
            ).start()

    def _serve_conn(self, conn: socket.socket, addr) -> None:
        _set_nodelay(conn)
        send_lock = threading.Lock()
        with self._conns_lock:
            if self._stop.is_set() or len(self._conns) >= self.max_connections:
                # connection cap: each live connection holds a reader
                # thread; without this bound any reachable peer could
                # spawn unbounded threads by dialing in a loop
                conn.close()
                return
            self._conns.add(conn)
        try:
            while not self._stop.is_set():
                req = _recv_frame(conn)
                if req is None:
                    return
                cost = self._frame_cost(req)
                with self._inflight_lock:
                    admitted = self._inflight < self.max_inflight
                    if admitted:
                        self._inflight += cost
                if not admitted:
                    # fail fast instead of queueing unbounded: the
                    # coordinator's retry/fallback stack absorbs this
                    # exactly like any other backend error
                    try:
                        with send_lock:
                            _send_frame(conn, {
                                "id": req.get("id"),
                                "error": f"replica overloaded "
                                         f"(>{self.max_inflight} in flight)",
                                "kind": "backend",
                            })
                    except OSError:
                        return
                    continue
                try:
                    self._pool.submit(self._serve_one, conn, send_lock, req)
                except RuntimeError:
                    with self._inflight_lock:
                        self._inflight -= cost
                    return  # pool shut down by close()
        except Exception as exc:
            # broad on purpose: _recv_frame's frame-size guard raises
            # BackendError, and ANY reader failure must take the logged
            # drop path, not kill the thread via excepthook
            if not self._stop.is_set():
                logger.warning("replica connection %s dropped: %s", addr, exc)
        finally:
            with self._conns_lock:
                self._conns.discard(conn)
            conn.close()

    def _serve_one(self, conn, send_lock, req: dict) -> None:
        rid = req.get("id")
        try:
            if req.get("op") == "rollout_swap":
                # Synchronous on this pool slot ON PURPOSE: the caller
                # staggers replicas one at a time and needs the verdict
                # before touching the next one; decision traffic on other
                # slots keeps flowing until the backend's own quiesce
                # barrier holds it (engine/local.run_quiesced). The
                # enclosing finally/send tail does the inflight decrement
                # and frame send exactly like a decision response.
                if self.swap_fn is None:
                    resp = {"id": rid, "ok": False,
                            "error": "replica has no swap hook"}
                else:
                    try:
                        detail = self.swap_fn(int(req["version"]))
                        resp = {"id": rid, "ok": True, "detail": detail}
                    except Exception as exc:
                        resp = {"id": rid, "ok": False, "error": str(exc)}
            elif req.get("op") == "prewarm":
                # Advisory prefix install forwarded by the coordinator
                # (engine/local.prewarm_prefix semantics). The response is
                # sent from the backend future's callback, so this pool
                # slot frees immediately (the return runs the finally's
                # inflight decrement); a backend without prewarm support
                # answers ok=False.
                self._serve_prewarm(conn, send_lock, req)
                return
            elif req.get("op") == "telemetry_pull":
                # Fleet telemetry fan-in (observability/fleetview.py):
                # stats + since-cursor trace slices + sampler ring, size-
                # capped so 16 replicas can't ship unbounded JSONL.
                resp = {"id": rid, **self._serve_telemetry(req)}
            elif req.get("op") == "decide_batch":
                # Prepacked admission (fleet/pools.py): many pods, ONE
                # nodes snapshot, one frame — per-pod outcomes ride back
                # positionally so one infeasible pod doesn't fail its
                # batchmates.
                resp = self._serve_batch(rid, req)
            else:
                pod = pod_from_wire(req["pod"])
                nodes = [node_from_wire(n) for n in req["nodes"]]
                work = req.get("work", "prefill")
                self._check_role(work)
                # Deadline budget riding the frame (sched/deadline.py):
                # the client stamped its REMAINING ms at send time. An
                # already-expired frame is refused before it can burn a
                # wave on a decision nobody is waiting for; otherwise the
                # budget is re-installed ambiently so a nested backend
                # (local engine behind this server) sees the same clock.
                wire_deadline = req.get("deadline_ms")
                budget = None
                if wire_deadline is not None:
                    if float(wire_deadline) <= 0.0:
                        raise DeadlineExceededError(
                            f"frame arrived with expired deadline "
                            f"({float(wire_deadline):.1f}ms remaining)"
                        )
                    budget = DeadlineBudget.start(float(wire_deadline))
                wire_trace = req.get("trace")
                if wire_trace and spans.enabled():
                    # Continue the COORDINATOR's trace on this side: same
                    # trace id, rooted under the caller's span, so the
                    # stitched tree shows exactly where the wire hop sits.
                    # The worker-side spans ride back in the response for
                    # the client to graft (ReplicaClient._resolve); the
                    # worker's own flight recorder keeps a copy too.
                    with spans.start_trace(
                        "replica.decide", layer="sched",
                        trace_id=str(wire_trace.get("trace_id")),
                        parent_id=str(wire_trace.get("span_id")),
                        pod=f"{pod.namespace}/{pod.name}",
                    ) as rtrace:
                        with deadline_mod.running(budget):
                            decision = self._decide(pod, nodes, work)
                    resp = {
                        "id": rid,
                        "decision": decision_to_wire(decision),
                        "spans": [s.to_dict() for s in rtrace.spans]
                        if rtrace is not None
                        else [],
                    }
                else:
                    with deadline_mod.running(budget):
                        decision = self._decide(pod, nodes, work)
                    resp = {"id": rid, "decision": decision_to_wire(decision)}
            with self._served_lock:
                self.served += 1
        except NoFeasibleNodeError as exc:
            resp = {"id": rid, "error": str(exc), "kind": "infeasible"}
        except DeadlineExceededError as exc:
            resp = {"id": rid, "error": str(exc), "kind": "deadline"}
        except Exception as exc:
            resp = {"id": rid, "error": str(exc), "kind": "backend"}
        finally:
            with self._inflight_lock:
                self._inflight -= self._frame_cost(req)
        try:
            with send_lock:
                _send_frame(conn, resp)
        except OSError:
            pass  # client gone; nothing to deliver to

    @staticmethod
    def _frame_cost(req: dict) -> int:
        """Admission weight of a frame against max_inflight. A
        decide_batch carries up to prepack_max_batch decisions — counting
        it as 1 would let an admission burst admit max_inflight*batch
        concurrent backend decisions, defeating the overload fail-fast
        exactly when prepacking concentrates load. A frame with headroom
        always admits (the predicate checks before adding), so a batch
        larger than max_inflight is still servable, one at a time."""
        if req.get("op") == "decide_batch":
            pods = req.get("pods")
            return max(1, len(pods)) if isinstance(pods, list) else 1
        return 1

    def _check_role(self, work: str) -> None:
        from k8s_llm_scheduler_tpu.fleet.pools import check_pool_role

        check_pool_role(self.pool_role, work)

    def _decide(
        self, pod: PodSpec, nodes: list[NodeMetrics], work: str
    ) -> SchedulingDecision:
        if self._backend_accepts_work:
            return self.backend.get_scheduling_decision(
                pod, nodes, work=work
            )
        return self.backend.get_scheduling_decision(pod, nodes)

    def _serve_telemetry(self, req: dict) -> dict:
        from k8s_llm_scheduler_tpu.observability import fleetview, spans

        if self.telemetry_fn is not None:
            return self.telemetry_fn(req)
        get_stats = getattr(self.backend, "get_stats", None)
        stats = get_stats() if get_stats is not None else {}
        return fleetview.build_telemetry(
            stats,
            spans.flight,
            since_seq=int(req.get("since", 0)),
            max_traces=min(
                int(req.get("max_traces", fleetview.DEFAULT_MAX_TRACES)),
                4 * fleetview.DEFAULT_MAX_TRACES,
            ),
            max_bytes=min(
                int(req.get("max_bytes", fleetview.DEFAULT_MAX_BYTES)),
                4 * fleetview.DEFAULT_MAX_BYTES,
            ),
        )

    def _serve_batch(self, rid, req: dict) -> dict:
        nodes = [node_from_wire(n) for n in req["nodes"]]
        work = req.get("work", "prefill")
        self._check_role(work)
        pods = [pod_from_wire(p) for p in req["pods"]]
        # deadline parity with _serve (the single-decision path): an
        # expired batch frame is refused BEFORE it can burn a prefill
        # wave, and the remaining budget is re-installed ambiently
        wire_deadline = req.get("deadline_ms")
        budget = None
        if wire_deadline is not None:
            if float(wire_deadline) <= 0.0:
                exc = DeadlineExceededError(
                    f"batch frame arrived with expired deadline "
                    f"({float(wire_deadline):.1f}ms remaining)"
                )
                return {"id": rid, "results": [
                    {"error": str(exc), "kind": "deadline"} for _ in pods
                ]}
            budget = DeadlineBudget.start(float(wire_deadline))
        results: list[dict] = []
        with deadline_mod.running(budget):
            if self._backend_batch is not None:
                # the backend's own batch surface (LocalLLMBackend
                # enqueues the whole pack before waiting — the engine
                # admits it as one prefill wave, which is the point of
                # prepacking)
                outcomes = self._backend_batch(pods, nodes, work=work)
            else:
                outcomes = []
                for pod in pods:
                    try:
                        outcomes.append(self._decide(pod, nodes, work))
                    except Exception as exc:
                        outcomes.append(exc)
        for outcome in outcomes:
            if isinstance(outcome, SchedulingDecision):
                results.append({"decision": decision_to_wire(outcome)})
            elif isinstance(outcome, NoFeasibleNodeError):
                results.append({"error": str(outcome), "kind": "infeasible"})
            elif isinstance(outcome, DeadlineExceededError):
                # degrade at the caller, don't retry, don't count a
                # breaker failure (sched/client.py non-failure contract)
                results.append({"error": str(outcome), "kind": "deadline"})
            else:
                results.append({"error": str(outcome), "kind": "backend"})
        return {"id": rid, "results": results}

    def _serve_prewarm(self, conn, send_lock, req: dict) -> None:
        rid = req.get("id")

        def reply(ok: bool) -> None:
            try:
                with send_lock:
                    _send_frame(conn, {"id": rid, "ok": ok})
            except OSError:
                pass  # client gone; nothing to deliver to

        fn = getattr(self.backend, "prewarm_prefix", None)
        if fn is None:
            reply(False)
            return
        try:
            nodes = [node_from_wire(n) for n in req["nodes"]]
            fut = fn(nodes)
        except Exception:
            logger.exception("replica prewarm failed")
            reply(False)
            return

        def _done(f) -> None:
            # Runs on the ENGINE worker thread (the backend resolves its
            # prewarm futures there): writing to a slow client socket here
            # would wedge ALL decision serving behind one blocked send.
            # Hand the reply to the request pool; the engine thread only
            # pays a submit.
            try:
                ok = bool(f.result())
            except Exception:
                ok = False
            try:
                self._pool.submit(reply, ok)
            except RuntimeError:
                pass  # pool shut down by close(); client is going away too

        fut.add_done_callback(_done)

    def close(self) -> None:
        self._stop.set()
        try:
            # shutdown BEFORE close: close alone does not wake a thread
            # blocked in accept(), so the join below would eat its full
            # timeout (measured 5s per server teardown)
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass  # not connected / already closed — fine
        try:
            self._sock.close()
        except OSError:
            pass
        # kill live connections too: a closed server must stop SERVING,
        # not just stop accepting (their blocked recvs need the shutdown
        # wake-up just like the listener's accept)
        with self._conns_lock:
            conns = list(self._conns)
        for conn in conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        self._accept_thread.join(timeout=5)
        self._pool.shutdown(wait=False, cancel_futures=True)


# ------------------------------------------------------------------- client
class ReplicaClient:
    """Multiplexing client for one remote replica.

    Thread-safe: any number of coordinator threads may call
    get_scheduling_decision concurrently; frames interleave on one socket
    and a reader thread resolves the per-id futures. A dead connection
    fails all in-flight requests with BackendError (the DecisionClient
    stack above retries / falls back / trips the breaker exactly as it
    would for a local backend fault).

    Connection lifecycle: LAZY and SELF-HEALING. The first submit dials;
    a dead/never-up replica surfaces as a fast BackendError per decision
    (absorbed by retry/fallback upstream — the coordinator must not crash
    because a worker is still loading weights), and every later submit
    re-dials, so a restarted worker heals without restarting the
    coordinator."""

    def __init__(self, host: str, port: int, connect_timeout_s: float = 10.0,
                 request_timeout_s: float = 60.0,
                 reconnect_base_s: float = 0.05,
                 reconnect_cap_s: float = 2.0) -> None:
        self.addr = f"{host}:{port}"
        self._host, self._port = host, port
        self.connect_timeout_s = connect_timeout_s
        self.request_timeout_s = request_timeout_s
        # Reconnect discipline: exponential backoff with jitter. Without
        # it, a worker restarting mid-stream eats one blocking dial
        # (connect_timeout_s each) PER in-flight decision retry — a
        # coordinator-side stall storm — and a fleet of coordinators
        # re-dialing in lockstep thundering-herds the worker the moment
        # it binds its socket. Failed dials open a fail-fast window
        # (decisions during it raise immediately and ride the upstream
        # retry/fallback stack); the window doubles per consecutive
        # failure up to reconnect_cap_s, jittered to ~U[0.5, 1.0)x so
        # herds decorrelate.
        self.reconnect_base_s = float(reconnect_base_s)
        self.reconnect_cap_s = float(reconnect_cap_s)
        self._dial_failures = 0
        self._next_dial_at = 0.0
        self._rng = random.Random()
        # Chaos seam (chaos/faults.py, seam "wire"): None in production —
        # one attribute read per frame. A chaos harness installs a Seam
        # here to inject resets/drops/dups/delays at the REAL framing
        # layer, below every retry/reconnect defense.
        self.fault_seam = None
        self._sock: socket.socket | None = None
        self._reader: threading.Thread | None = None
        self._conn_lock = threading.Lock()
        self._send_lock = threading.Lock()
        self._pending: dict[int, Future] = {}
        self._pending_lock = threading.Lock()
        self._ids = itertools.count()
        self._closed = False
        # Batched decision-frame flushing: concurrent submitters enqueue
        # encoded frames here; whoever holds the send lock drains the
        # WHOLE outbox as one vectored sendmsg (_send_frames), and threads
        # whose frames were flushed for them (rid in _flushed) return
        # without a syscall. Opportunistic — no timer, no added latency:
        # a lone frame flushes immediately, a burst's leaders coalesce
        # exactly when they contend.
        self._outbox: deque[tuple[int, bytes, bytes]] = deque()
        self._flushed: set[int] = set()
        self._outbox_lock = threading.Lock()
        # Wire-path counters (wire_stats): persistent-connection reuse and
        # flush batching are measured, not assumed.
        self._wire = {
            "dials": 0,
            "frames_sent": 0,
            "flushes": 0,
            "batched_frames": 0,
            "max_batch": 0,
            "bytes_sent": 0,
        }

    def _ensure_connected(self) -> tuple[socket.socket, threading.Thread]:
        """Dial (or re-dial) the replica. Serialized so concurrent submits
        after a drop produce one reconnect, not a stampede."""
        with self._conn_lock:
            if self._closed:
                raise BackendError(f"replica {self.addr} client closed")
            if self._sock is not None and (
                self._reader is not None and self._reader.is_alive()
            ):
                return self._sock, self._reader
            # previous socket (if any) is dead: drop it and re-dial
            if self._sock is not None:
                try:
                    self._sock.close()
                except OSError:
                    pass
                self._sock = None
            now = time.monotonic()
            if self._dial_failures and now < self._next_dial_at:
                # fail-fast window after a failed dial: don't pay another
                # blocking connect (or hammer a restarting worker) until
                # the backoff expires
                raise BackendError(
                    f"replica {self.addr} unreachable "
                    f"(reconnect backing off "
                    f"{self._next_dial_at - now:.2f}s after "
                    f"{self._dial_failures} failed dial(s))"
                )
            try:
                sock = socket.create_connection(
                    (self._host, self._port), self.connect_timeout_s
                )
            except OSError as exc:
                self._dial_failures += 1
                if self._dial_failures >= 2:
                    # the FIRST failure keeps the historical contract (the
                    # very next submit may re-dial immediately — a worker
                    # that just finished binding its socket heals with
                    # zero added latency); only repetition opens a window
                    delay = min(
                        self.reconnect_cap_s,
                        self.reconnect_base_s
                        * (2 ** min(self._dial_failures - 2, 16)),
                    )
                    self._next_dial_at = now + delay * (
                        0.5 + 0.5 * self._rng.random()
                    )
                raise BackendError(
                    f"replica {self.addr} unreachable: {exc}"
                ) from exc
            self._dial_failures = 0
            self._next_dial_at = 0.0
            # create_connection leaves its timeout ON THE SOCKET: the
            # reader would then die on any response slower than
            # connect_timeout_s (e.g. a first decision paying a jit
            # compile). Per-request deadlines are enforced at
            # fut.result(request_timeout_s); the socket itself blocks
            # indefinitely — with TCP KEEPALIVE on, so a HALF-OPEN peer
            # (host preempted without FIN/RST) eventually kills the
            # reader and the next submit re-dials instead of the reader
            # blocking in recv forever.
            sock.settimeout(None)
            _set_nodelay(sock)
            with self._outbox_lock:
                self._wire["dials"] += 1
            try:
                sock.setsockopt(socket.SOL_SOCKET, socket.SO_KEEPALIVE, 1)
                if hasattr(socket, "TCP_KEEPIDLE"):
                    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_KEEPIDLE, 30)
                    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_KEEPINTVL, 10)
                    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_KEEPCNT, 3)
            except OSError:
                pass  # keepalive is best-effort hardening
            self._sock = sock
            reader = threading.Thread(
                target=self._read_loop, args=(sock,), daemon=True,
                name=f"replica-client-{self._port}",
            )
            self._reader = reader
            reader.start()
            return sock, reader

    def _mark_suspect(self, sock: socket.socket) -> None:
        """A request timed out: the connection may be half-open (peer gone
        without FIN/RST — keepalive takes ~minutes). Shut the socket so the
        reader dies, in-flight futures fail fast, and the next submit
        re-dials; if the replica was merely slow, the re-dial is cheap.

        `sock` is the connection the timed-out request was SUBMITTED on:
        if another thread already re-dialed (self._sock replaced), shutting
        down the current socket would spuriously kill a healthy connection
        and every request in flight on it."""
        with self._conn_lock:
            if sock is not self._sock:
                return  # stale connection already replaced; nothing to kill
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass

    def _read_loop(self, sock: socket.socket) -> None:
        try:
            while True:
                resp = _recv_frame(sock)
                if resp is None:
                    break
                with self._pending_lock:
                    fut = self._pending.pop(resp.get("id"), None)
                if fut is not None and not fut.done():
                    fut.set_result(resp)
        except Exception as exc:  # OSError, desync, MAX_FRAME BackendError…
            # ANY reader death must fall through to the in-flight-failure
            # sweep below — a narrower catch once let a BackendError from
            # the frame-size check skip it, leaving callers to block out
            # their full request timeout with no error ever surfaced.
            if not self._closed:
                logger.warning("replica client %s reader died: %r", self.addr, exc)
        # connection is gone: fail everything in flight (the next submit
        # re-dials via _ensure_connected)
        with self._pending_lock:
            pending, self._pending = self._pending, {}
        for fut in pending.values():
            if not fut.done():
                fut.set_exception(
                    BackendError(f"replica {self.addr} connection lost")
                )

    def _flush_frames(
        self,
        sock: socket.socket,
        rid: int,
        frames: list[tuple[bytes, bytes]],
    ) -> None:
        """Put request `rid`'s encoded frames on the wire through the
        shared outbox. The holder of the send lock drains EVERYTHING
        queued as one vectored write, so a burst's concurrent decision
        frames coalesce into one syscall exactly when they contend —
        and a thread that finds its frames already flushed returns
        without touching the socket.

        Send failure semantics: frames the failing flush carried for
        OTHER requests fail through their futures (indistinguishable
        from a reset-after-send, which the reader sweep also produces);
        the flusher's own request raises, matching the historical
        single-frame contract."""
        with self._outbox_lock:
            for header, payload in frames:
                self._outbox.append((rid, header, payload))
        while True:
            with self._outbox_lock:
                if rid in self._flushed:
                    self._flushed.discard(rid)
                    return
            with self._send_lock:
                with self._outbox_lock:
                    batch = list(self._outbox)
                    self._outbox.clear()
                if not batch:
                    continue  # flushed by the previous holder; re-check
                mine = any(r == rid for r, _, _ in batch)
                # Re-resolve the LIVE socket at flush time: the batch may
                # carry frames enqueued against a connection that healed
                # while this thread waited on the send lock — writing
                # them to the stale captured socket would spuriously fail
                # healthy requests. (If no live socket exists the stale
                # one fails exactly as a dead connection should.)
                with self._conn_lock:
                    live = self._sock or sock
                try:
                    _send_frames(live, [(h, p) for _, h, p in batch])
                except OSError as exc:
                    with self._pending_lock:
                        failed = [
                            self._pending.pop(r, None)
                            for r, _, _ in batch
                            if r != rid
                        ]
                    for fut in failed:
                        if fut is not None and not fut.done():
                            fut.set_exception(BackendError(
                                f"replica {self.addr} send failed: {exc}"
                            ))
                    with self._outbox_lock:
                        for r, _, _ in batch:
                            if r != rid:
                                self._flushed.add(r)
                    if mine:
                        raise
                    continue
                with self._outbox_lock:
                    for r, _, _ in batch:
                        self._flushed.add(r)
                    self._wire["flushes"] += 1
                    self._wire["frames_sent"] += len(batch)
                    if len(batch) > 1:
                        self._wire["batched_frames"] += len(batch)
                    self._wire["max_batch"] = max(
                        self._wire["max_batch"], len(batch)
                    )
                    self._wire["bytes_sent"] += sum(
                        len(h) + len(p) for _, h, p in batch
                    )

    def wire_stats(self) -> dict:
        """Copy of the wire-path counters: dials (persistent-connection
        reuse shows here — a healthy client dials once per connection
        lifetime, not per frame), frames vs flushes (batching ratio),
        bytes."""
        with self._outbox_lock:
            return dict(self._wire)

    def _submit_frame(
        self, payload: dict
    ) -> tuple[int, Future, socket.socket]:
        """Allocate an id, register the pending future, and send
        `payload` (id added) — THE single copy of the registration/send/
        reader-death protocol, shared by decisions and prewarms so a fix
        to its subtleties can never drift between them."""
        sock, reader = self._ensure_connected()
        fault = None
        if self.fault_seam is not None:
            pod = payload.get("pod")
            key = pod.get("name") if isinstance(pod, dict) else payload.get("op")
            fault_delay = self.fault_seam.delay_s(key=key)
            if fault_delay > 0:
                time.sleep(fault_delay)  # graftlint: ok[raw-clock] — chaos-injected wire latency; inert (seam is None) in production
            for kind in ("reset", "drop", "dup"):
                if self.fault_seam.should(kind, key=key) is not None:
                    fault = kind
                    break
        rid = next(self._ids)
        fut: Future = Future()
        with self._pending_lock:
            if self._closed:
                raise BackendError(f"replica {self.addr} client closed")
            self._pending[rid] = fut
        try:
            # drop: frame never leaves — the caller times out. reset: the
            # connection dies before the response could ever land — the
            # frame is withheld too, because "sent, then reset" would race
            # the server's reply against the shutdown and the winner would
            # be thread timing (chaos runs must be deterministic); from
            # the caller the two shapes are indistinguishable either way.
            if fault not in ("drop", "reset"):
                frames = [_encode_frame({"id": rid, **payload})]
                if fault == "dup":
                    # duplicate frame, same id: the server serves it
                    # twice and the second response must be a no-op
                    # at the client (pending entry already popped)
                    frames.append(_encode_frame({"id": rid, **payload}))
                self._flush_frames(sock, rid, frames)
        except OSError as exc:
            with self._pending_lock:
                self._pending.pop(rid, None)
            raise BackendError(f"replica {self.addr} send failed: {exc}") from exc
        if fault == "reset":
            # mid-decision connection reset: the reader's fail-everything
            # sweep and the next submit's re-dial are the paths under test
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        if not reader.is_alive():
            # TOCTOU guard: the reader may have died (and run its
            # fail-everything sweep) BETWEEN the liveness check and our
            # future registration — a first write after FIN can land in
            # the send buffer without EPIPE, leaving this future orphaned
            # with nobody to resolve it. Fail it ourselves.
            with self._pending_lock:
                self._pending.pop(rid, None)
            if not fut.done():
                fut.set_exception(
                    BackendError(f"replica {self.addr} connection lost")
                )
        return rid, fut, sock

    def _submit(
        self, pod: PodSpec, nodes: Sequence[NodeMetrics],
        work: str | None = None,
    ) -> tuple[int, Future, socket.socket]:
        payload = {
            "pod": pod_to_wire(pod),
            "nodes": [node_to_wire(n) for n in nodes],
        }
        if work is not None:
            # disaggregated-pool tag (fleet/pools.py): lets a decode-role
            # worker refuse misrouted admission work
            payload["work"] = work
        # Deadline budget rides the frame (sched/deadline.py): stamp the
        # REMAINING ms at send time so the worker judges against what the
        # decision actually has left, wire transit included.
        remaining = deadline_mod.remaining_ms()
        if remaining is not None:
            payload["deadline_ms"] = round(remaining, 3)
        # Trace propagation: the ambient decision trace's (trace_id,
        # span_id) rides the frame so the worker's spans stitch into ONE
        # cross-host tree (ReplicaServer returns them in the response).
        wire_trace = spans.wire_context()
        if wire_trace is not None:
            payload["trace"] = wire_trace
        return self._submit_frame(payload)

    def prewarm_prefix(self, nodes: Sequence[NodeMetrics]) -> Future:
        """Forward an advisory prefix install to the worker's backend
        (engine/local.prewarm_prefix over the wire). The future resolves
        bool for an ANSWERED advisory (True installed / False dropped —
        both mean the worker is alive) and raises BackendError on
        TRANSPORT failure (connect/send/reader-death/deadline) — the
        distinction FanoutBackend's health gating needs: drops are
        healthy, transport failures feed the cooldown. Deadline-bounded
        by request_timeout_s so a worker that accepts the frame but never
        replies (engine stuck in a long compile) cannot wedge this future
        — or the scheduler's _prewarm_last signature — forever."""
        out: Future = Future()
        try:
            rid, fut, _sock = self._submit_frame({
                "op": "prewarm",
                "nodes": [node_to_wire(n) for n in nodes],
            })
        except Exception as exc:
            out.set_exception(
                BackendError(f"replica {self.addr} prewarm: {exc}")
            )
            return out

        def _expire() -> None:
            self._drop(rid)
            if not out.done():
                out.set_exception(
                    BackendError(
                        f"replica {self.addr} prewarm unanswered after "
                        f"{self.request_timeout_s}s"
                    )
                )

        timer = threading.Timer(self.request_timeout_s, _expire)
        timer.daemon = True

        def _done(f) -> None:
            timer.cancel()
            if out.done():
                return
            try:
                resp = f.result()
                out.set_result(bool(resp.get("ok")))
            except Exception as exc:
                out.set_exception(
                    BackendError(f"replica {self.addr} prewarm: {exc}")
                )

        fut.add_done_callback(_done)
        timer.start()
        return out

    def rollout_swap(self, version: int, timeout_s: float | None = None) -> dict:
        """Ask the worker to hot-swap its backend to a registry version
        (ReplicaServer swap_fn). BLOCKING — the canary controller staggers
        replicas one at a time and needs this replica's verdict before
        touching the next (rollout/canary.staggered_swap). Returns the
        server's {"ok", "detail"|"error"} payload; transport failures raise
        BackendError. `timeout_s` defaults to request_timeout_s — raise it
        for donate-mode swaps whose restore runs inside the pause."""
        rid, fut, sock = self._submit_frame({
            "op": "rollout_swap", "version": int(version),
        })
        try:
            resp = fut.result(
                timeout=self.request_timeout_s if timeout_s is None else timeout_s
            )
        except FuturesTimeout as exc:
            self._drop(rid)
            self._mark_suspect(sock)
            raise BackendError(
                f"replica {self.addr} swap timed out"
            ) from exc
        return {k: v for k, v in resp.items() if k != "id"}

    def telemetry_pull(
        self,
        since_seq: int = 0,
        max_traces: int | None = None,
        max_bytes: int | None = None,
        timeout_s: float | None = None,
    ) -> dict:
        """Pull this worker's telemetry payload (stats tree with embedded
        histogram buckets, flight-recorder slice since `since_seq`,
        sampler ring — observability/fleetview.build_telemetry shape).
        BLOCKING, like rollout_swap: the aggregator drives one bounded
        pull per source per round, and a dead worker must surface as a
        BackendError the aggregator can mark stale on, not a hang."""
        payload: dict[str, Any] = {
            "op": "telemetry_pull", "since": int(since_seq),
        }
        if max_traces is not None:
            payload["max_traces"] = int(max_traces)
        if max_bytes is not None:
            payload["max_bytes"] = int(max_bytes)
        rid, fut, sock = self._submit_frame(payload)
        try:
            resp = fut.result(
                timeout=self.request_timeout_s if timeout_s is None else timeout_s
            )
        except FuturesTimeout as exc:
            self._drop(rid)
            self._mark_suspect(sock)
            raise BackendError(
                f"replica {self.addr} telemetry pull timed out"
            ) from exc
        if "stats" not in resp:
            raise BackendError(
                f"replica {self.addr}: "
                f"{resp.get('error', 'malformed telemetry response')}"
            )
        return {k: v for k, v in resp.items() if k != "id"}

    def _resolve(self, resp: dict) -> SchedulingDecision:
        if "decision" in resp:
            remote_spans = resp.get("spans")
            if remote_spans:
                trace = spans.current_trace()
                if trace is not None:
                    # merge_remote_spans drops spans whose trace id does
                    # not match — a desynced frame cannot pollute the tree
                    trace.merge_remote_spans(remote_spans)
            return decision_from_wire(resp["decision"])
        if resp.get("kind") == "infeasible":
            raise NoFeasibleNodeError(resp.get("error", ""))
        if resp.get("kind") == "deadline":
            # the worker refused an expired frame: degrade, don't retry
            # (and don't count a breaker failure — sched/client.py)
            raise DeadlineExceededError(resp.get("error", ""))
        raise BackendError(
            f"replica {self.addr}: {resp.get('error', 'unknown failure')}"
        )

    def _drop(self, rid: int) -> None:
        with self._pending_lock:
            self._pending.pop(rid, None)

    def _resolve_batch(
        self, resp: dict
    ) -> list["SchedulingDecision | Exception"]:
        """Positional per-pod outcomes of a decide_batch: a decision, a
        NoFeasibleNodeError, or a BackendError — returned, not raised,
        so one bad pod never fails its batchmates."""
        if "results" not in resp:
            raise BackendError(
                f"replica {self.addr}: {resp.get('error', 'malformed batch response')}"
            )
        out: list[SchedulingDecision | Exception] = []
        for entry in resp["results"]:
            if "decision" in entry:
                out.append(decision_from_wire(entry["decision"]))
            elif entry.get("kind") == "infeasible":
                out.append(NoFeasibleNodeError(entry.get("error", "")))
            elif entry.get("kind") == "deadline":
                out.append(DeadlineExceededError(entry.get("error", "")))
            else:
                out.append(BackendError(
                    f"replica {self.addr}: "
                    f"{entry.get('error', 'unknown failure')}"
                ))
        return out

    def _submit_batch(
        self, pods: Sequence[PodSpec], nodes: Sequence[NodeMetrics],
        work: str | None,
    ) -> tuple[int, Future, socket.socket]:
        payload = {
            "op": "decide_batch",
            "pods": [pod_to_wire(p) for p in pods],
            "nodes": [node_to_wire(n) for n in nodes],
        }
        if work is not None:
            payload["work"] = work
        # the batch shares one deadline budget, same stamp as _submit —
        # without it prepacked admission would silently opt out of the
        # degradation ladder
        remaining = deadline_mod.remaining_ms()
        if remaining is not None:
            payload["deadline_ms"] = round(remaining, 3)
        return self._submit_frame(payload)

    def get_scheduling_decisions_batch(
        self, pods: Sequence[PodSpec], nodes: Sequence[NodeMetrics],
        work: str | None = None,
    ) -> list["SchedulingDecision | Exception"]:
        """Prepacked admission: ship `pods` (sharing ONE snapshot) as a
        single decide_batch frame; the worker's engine admits them
        together and coalesces them into one prefill wave."""
        rid, fut, sock = self._submit_batch(pods, nodes, work)
        try:
            resp = fut.result(timeout=self.request_timeout_s)
        except FuturesTimeout as exc:
            self._drop(rid)
            self._mark_suspect(sock)
            raise BackendError(
                f"replica {self.addr} batch timed out after "
                f"{self.request_timeout_s}s"
            ) from exc
        return self._resolve_batch(resp)

    async def get_scheduling_decisions_batch_async(
        self, pods: Sequence[PodSpec], nodes: Sequence[NodeMetrics],
        work: str | None = None,
    ) -> list["SchedulingDecision | Exception"]:
        import asyncio

        rid, fut, sock = self._submit_batch(pods, nodes, work)
        try:
            resp = await asyncio.wait_for(
                asyncio.wrap_future(fut), timeout=self.request_timeout_s
            )
        except (TimeoutError, asyncio.TimeoutError) as exc:
            self._drop(rid)
            self._mark_suspect(sock)
            raise BackendError(
                f"replica {self.addr} batch timed out after "
                f"{self.request_timeout_s}s"
            ) from exc
        return self._resolve_batch(resp)

    def get_scheduling_decision(
        self, pod: PodSpec, nodes: Sequence[NodeMetrics],
        work: str | None = None,
    ) -> SchedulingDecision:
        rid, fut, sock = self._submit(pod, nodes, work)
        try:
            resp = fut.result(timeout=self.request_timeout_s)
        except FuturesTimeout as exc:
            # drop the pending entry (it would otherwise leak for the
            # connection's lifetime), mark the connection suspect (a
            # half-open peer would otherwise stall EVERY later request by
            # the full timeout), and surface the documented failure type
            self._drop(rid)
            self._mark_suspect(sock)
            raise BackendError(
                f"replica {self.addr} timed out after {self.request_timeout_s}s"
            ) from exc
        return self._resolve(resp)

    async def get_scheduling_decision_async(
        self, pod: PodSpec, nodes: Sequence[NodeMetrics],
        work: str | None = None,
    ) -> SchedulingDecision:
        """Natively-async variant (DecisionClient prefers it): awaits the
        wire future without holding a worker thread, so a burst's leaders
        fan out to replicas without being capped by the to_thread pool."""
        import asyncio

        rid, fut, sock = self._submit(pod, nodes, work)
        try:
            resp = await asyncio.wait_for(
                asyncio.wrap_future(fut), timeout=self.request_timeout_s
            )
        except (TimeoutError, asyncio.TimeoutError) as exc:
            self._drop(rid)
            self._mark_suspect(sock)
            raise BackendError(
                f"replica {self.addr} timed out after {self.request_timeout_s}s"
            ) from exc
        return self._resolve(resp)

    def close(self) -> None:
        with self._pending_lock:
            self._closed = True
        with self._conn_lock:
            sock, reader = self._sock, self._reader
            self._sock = None
        if sock is not None:
            try:
                # shutdown wakes the reader blocked in recv (close alone
                # does not — it parked the join below for its full timeout)
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        if reader is not None:
            reader.join(timeout=5)


# ------------------------------------------------------------------ fan-out
class _ReplicaHealth:
    """Per-replica dispatch state: in-flight count, latency EMA, failure
    cooldown. Mutated under the owning FanoutBackend's lock."""

    __slots__ = ("inflight", "ema_s", "failures", "cooldown_until", "probing")

    def __init__(self) -> None:
        self.inflight = 0
        self.ema_s = 0.0  # 0 = no sample yet (treated as fast/unknown)
        self.failures = 0
        self.cooldown_until = 0.0
        # set when the current request is a starvation probe: its sample
        # REPLACES the (stale) EMA instead of blending — the whole point
        # of the probe is re-measurement
        self.probing = False


class FanoutBackend:
    """Health-aware decision dispatch across [local backend, replicas...].

    Sits at the DecisionBackend seam, below cache/single-flight: only
    leader decisions reach it, so replica count multiplies exactly the
    model compute (shared-prefix economics hold on every replica
    independently — each re-prefills the burst's snapshot prefix once).

    Dispatch is weighted least-load, not round-robin (VERDICT r4 weak #7:
    one slow or half-dead replica round-robined 1/N of every burst into
    its queue and inflated the whole burst's tail). Each replica carries
    (in-flight count, latency EMA, failure cooldown); a request routes to
    the replica minimizing (inflight + 1) * ema_latency — so a 10x-slower
    replica organically receives ~1/10 of the traffic instead of 1/N —
    and a replica that throws enters exponential cooldown (capped) so a
    dead host drops out of rotation entirely until it heals. Failures
    still surface as the BackendError the retry/breaker/fallback stack
    above already handles."""

    COOLDOWN_BASE_S = 0.5
    COOLDOWN_CAP_S = 30.0
    EMA_ALPHA = 0.2
    # A replica not routed to for PROBE_IDLE_S gets one probe request: the
    # EMA only updates on routed requests, so without re-probing one
    # transient slow sample (cold compile, GC pause) would starve a
    # healthy replica forever. Two gates bound the probe cost:
    # - TIME (idle >= PROBE_IDLE_S): pick-counted probes at burst rates
    #   would re-route a slow replica's full latency into the burst every
    #   N decisions (~30% capacity at 400/s measured);
    # - COUNT (>= PROBE_EVERY_PICKS dispatches since the last probe):
    #   under SPARSE traffic (inter-arrival > PROBE_IDLE_S) the time gate
    #   alone would make every request a probe, degenerating dispatch to
    #   alternation — the count gate caps probes at 1/PROBE_EVERY_PICKS
    #   of traffic regardless of rate.
    PROBE_IDLE_S = 5.0
    PROBE_EVERY_PICKS = 8
    # Replicas slower than SLOW_EXCLUDE_RATIO x the fastest EMA receive
    # no cost-picked traffic at all (probes only): decisions are latency-
    # sensitive, and inflight pressure on the fast replicas would
    # otherwise leak band-tied picks onto a 10x replica exactly at burst
    # peaks — where its full latency lands on the burst's tail.
    SLOW_EXCLUDE_RATIO = 4.0

    def __init__(
        self,
        replicas: Sequence[Any],
        clock: "Callable[[], float]" = time.monotonic,
    ) -> None:
        if not replicas:
            raise ValueError("FanoutBackend needs at least one replica")
        self.replicas = list(replicas)
        self.routed = [0] * len(self.replicas)
        self._health = [_ReplicaHealth() for _ in self.replicas]
        self._lock = threading.Lock()
        self._rr = itertools.count()  # tiebreak rotation among equals
        # Injectable time source: every probe-window / cooldown / EMA
        # judgment reads THIS clock, so tests can advance time explicitly
        # instead of racing real sleeps on a loaded host (VERDICT r5 #6).
        self._clock = clock
        self._last_routed_t = [self._clock()] * len(self.replicas)
        self._picks_total = 0
        self._last_probe_pick = 0

    # ------------------------------------------------------------- dispatch
    def _pick(self) -> int:
        """Weighted least-load choice; replicas in failure cooldown are
        skipped unless ALL are cooling down (then least-bad is used — a
        decision must still be attempted so the upstream stack can fall
        back on a real error, not on dispatch refusal)."""
        now = self._clock()
        rotate = next(self._rr)
        with self._lock:
            candidates = [
                i for i, h in enumerate(self._health)
                if h.cooldown_until <= now
            ]
            if not candidates:
                candidates = list(range(len(self.replicas)))
            # starvation probe: a candidate idle past PROBE_IDLE_S gets
            # this request so its EMA can recover — at most one probe per
            # PROBE_EVERY_PICKS dispatches (see class comment)
            self._picks_total += 1
            starved = [
                i for i in candidates
                if now - self._last_routed_t[i] >= self.PROBE_IDLE_S
            ]
            if starved and (
                self._picks_total - self._last_probe_pick
                >= self.PROBE_EVERY_PICKS
            ):
                i = min(starved, key=lambda j: self._last_routed_t[j])
                self._last_probe_pick = self._picks_total
                self._last_routed_t[i] = now
                self._health[i].inflight += 1
                self._health[i].probing = True
                self.routed[i] += 1
                return i

            # slow exclusion: drop way-slower replicas from the cost pick
            # (probes above keep their EMAs fresh so they can rejoin)
            min_ema = min(
                (h.ema_s for h in self._health if h.ema_s), default=0.0
            )
            if min_ema:
                fast_enough = [
                    i for i in candidates
                    if not self._health[i].ema_s
                    or self._health[i].ema_s
                    <= self.SLOW_EXCLUDE_RATIO * min_ema
                ]
                if fast_enough:
                    candidates = fast_enough

            def cost(i: int) -> tuple:
                h = self._health[i]
                # unknown latency ranks as the fastest observed (optimistic
                # first sample). The load score is BANDED (~25% classes):
                # µs-level EMA noise between equal replicas must not make
                # one a permanent winner under sequential traffic — within
                # a band the rotation index shares work evenly.
                ema = h.ema_s or min_ema
                score = (h.inflight + 1) * (ema or 1e-6)
                band = int(math.log(score) / math.log(1.25))
                return (band, (i + rotate) % len(self.replicas))

            i = min(candidates, key=cost)
            self._last_routed_t[i] = now
            self._health[i].inflight += 1
            self.routed[i] += 1
            return i

    def _record(
        self,
        i: int,
        elapsed_s: float | None,
        failed: bool,
        adjust_inflight: bool = True,
    ) -> None:
        with self._lock:
            h = self._health[i]
            if adjust_inflight:
                h.inflight = max(0, h.inflight - 1)
            if failed:
                self._note_failure_locked(h)
            else:
                h.failures = 0
                h.cooldown_until = 0.0
                if elapsed_s is not None:
                    h.ema_s = (
                        elapsed_s if (h.ema_s == 0.0 or h.probing)
                        else (1 - self.EMA_ALPHA) * h.ema_s
                        + self.EMA_ALPHA * elapsed_s
                    )
            h.probing = False

    def _note_failure_locked(self, h: _ReplicaHealth) -> None:
        """Exponential-backoff cooldown bump (caller holds self._lock)."""
        h.failures += 1
        backoff = min(
            self.COOLDOWN_CAP_S,
            self.COOLDOWN_BASE_S * (2 ** min(h.failures - 1, 8)),
        )
        h.cooldown_until = self._clock() + backoff

    def _record_advisory_failure(self, i: int) -> None:
        """Prewarm TRANSPORT failure: feed the cooldown, and ONLY the
        cooldown. Deliberately not _record (ADVICE round 5): an advisory
        completion must not reset `failures`/`cooldown_until` on success —
        a healthy prewarm answer from a replica mid-cooldown would
        re-admit it before its decision backoff expired — and must not
        clear `probing`, which belongs to an in-flight DECISION probe the
        prewarm knows nothing about."""
        with self._lock:
            self._note_failure_locked(self._health[i])

    def prewarm_prefix(self, nodes: Sequence[NodeMetrics]):
        """Fan the advisory prefix install out to every replica that
        supports it AND is not in failure cooldown (shared-prefix
        economics hold per replica — each one pays its own cluster-state
        prefill on the first leader otherwise).

        Health integration: a TRANSPORT failure (connect/send/deadline —
        the replica client raises) feeds the same exponential cooldown
        decisions use, so a black-holed worker costs at most one blocking
        dial per cooldown expiry instead of one per prewarm tick. Any
        ANSWERED advisory (installed or dropped) is health-neutral: it
        neither clears decision failure state nor touches an in-flight
        probe (_record_advisory_failure). Cooling replicas are skipped
        outright.

        Returns None when no replica supports prewarming (disables the
        scheduler's prewarm loop), else a Future resolving True iff every
        replica that was actually forwarded to installed — False (any
        drop, any failure, or everyone cooling) re-arms the loop's retry
        on its next idle tick."""
        now = self._clock()
        futs: list[tuple[int, Future]] = []
        supported = 0
        for i, r in enumerate(self.replicas):
            fn = getattr(r, "prewarm_prefix", None)
            if fn is None:
                continue
            supported += 1
            with self._lock:
                cooling = self._health[i].cooldown_until > now
            if cooling:
                continue
            futs.append((i, fn(nodes)))
        if not supported:
            return None
        out: Future = Future()
        if not futs:  # all supported replicas cooling: retry next tick
            out.set_result(False)
            return out
        state = {"left": len(futs), "ok": True}
        lock = threading.Lock()

        def _done(i: int, f: Future) -> None:
            try:
                ok = bool(f.result())
                failed = False
            except Exception:
                ok, failed = False, True
            if failed:
                # failure path only: successes (installed OR dropped) are
                # advisory and must not touch decision health state
                self._record_advisory_failure(i)
            with lock:
                state["ok"] &= ok
                state["left"] -= 1
                finished = state["left"] == 0
            if finished and not out.done():
                out.set_result(state["ok"])

        for i, f in futs:
            f.add_done_callback(lambda fut, i=i: _done(i, fut))
        return out

    def get_scheduling_decision(
        self, pod: PodSpec, nodes: Sequence[NodeMetrics]
    ) -> SchedulingDecision:
        i = self._pick()
        start = self._clock()
        failed = False
        elapsed = None
        # accounting in finally: a BaseException (e.g. asyncio
        # cancellation propagating through to_thread) must still release
        # the inflight slot — a leak here permanently skews dispatch away
        # from a healthy replica. Cancellation records neither latency nor
        # failure: it is not the replica's fault.
        try:
            decision = self.replicas[i].get_scheduling_decision(pod, nodes)
            elapsed = self._clock() - start
            return decision
        except NoFeasibleNodeError:
            # a correct "no" is a healthy, fast answer — not a failure
            elapsed = self._clock() - start
            raise
        except Exception:
            failed = True
            raise
        finally:
            self._record(i, elapsed, failed=failed)

    async def get_scheduling_decision_async(
        self, pod: PodSpec, nodes: Sequence[NodeMetrics]
    ) -> SchedulingDecision:
        """Async routing: without this, wrapping a backend in FanoutBackend
        would hide the replicas' native async paths from DecisionClient and
        throttle every leader through the default to_thread pool (~32
        threads) — the exact bottleneck the async path exists to avoid."""
        import asyncio

        i = self._pick()
        replica = self.replicas[i]
        start = self._clock()
        failed = False
        elapsed = None
        try:
            fn = getattr(replica, "get_scheduling_decision_async", None)
            if fn is not None:
                decision = await fn(pod, nodes)
            else:
                decision = await asyncio.to_thread(
                    replica.get_scheduling_decision, pod, nodes
                )
            elapsed = self._clock() - start
            return decision
        except NoFeasibleNodeError:
            elapsed = self._clock() - start
            raise
        except Exception:
            failed = True
            raise
        finally:
            # finally, not except: CancelledError must release the
            # inflight slot (without a latency sample or a cooldown)
            self._record(i, elapsed, failed=failed)

    def get_stats(self) -> dict:
        with self._lock:
            stats: dict[str, Any] = {
                "fanout_routed": list(self.routed),
                "fanout_ema_ms": [
                    round(h.ema_s * 1000.0, 2) for h in self._health
                ],
                "fanout_cooling": [
                    h.cooldown_until > self._clock() for h in self._health
                ],
            }
        local = self.replicas[0]
        if hasattr(local, "get_stats"):
            stats.update(local.get_stats())
        return stats

    def close(self) -> None:
        for r in self.replicas:
            if hasattr(r, "close"):
                r.close()
