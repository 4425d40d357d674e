"""Cross-host decision serving (sched/replica.py): wire protocol,
multiplexing client, fan-out routing, failure propagation — all over real
localhost sockets with the stub backend (no model weights)."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from k8s_llm_scheduler_tpu.engine.backend import (
    BackendError,
    NoFeasibleNodeError,
    StubBackend,
)
from k8s_llm_scheduler_tpu.testing import async_deadline
from k8s_llm_scheduler_tpu.sched.replica import (
    FanoutBackend,
    ReplicaClient,
    ReplicaServer,
    decision_from_wire,
    decision_to_wire,
)
from k8s_llm_scheduler_tpu.types import DecisionSource, NodeMetrics, PodSpec


def make_nodes(n=3):
    return [
        NodeMetrics(
            name=f"node-{i}", cpu_usage_percent=10.0 * (i + 1),
            memory_usage_percent=10.0 * (i + 1), available_cpu_cores=8.0,
            available_memory_gb=32.0, pod_count=i, max_pods=110,
            labels={"zone": "z1"}, taints=(),
            conditions={"Ready": "True"},
        )
        for i in range(n)
    ]


def make_pod(i=0):
    return PodSpec(
        name=f"p{i}", namespace="default", cpu_request=0.1,
        memory_request=0.125, node_selector={}, tolerations=(
            {"key": "gpu", "operator": "Exists", "value": "", "effect": ""},
        ),
        priority=3,
    )


@pytest.fixture
def server():
    srv = ReplicaServer(StubBackend(), host="127.0.0.1", port=0)
    yield srv
    srv.close()


class TestWire:
    def test_decision_roundtrip(self):
        from k8s_llm_scheduler_tpu.types import SchedulingDecision

        d = SchedulingDecision(
            selected_node="node-2", confidence=0.87, reasoning="because",
            source=DecisionSource.LLM, latency_ms=12.5,
        )
        assert decision_from_wire(decision_to_wire(d)) == d


class TestClientServer:
    def test_remote_decision_matches_local(self, server):
        client = ReplicaClient("127.0.0.1", server.port)
        try:
            local = StubBackend()
            pod, nodes = make_pod(), make_nodes()
            remote_d = client.get_scheduling_decision(pod, nodes)
            local_d = local.get_scheduling_decision(pod, nodes)
            assert remote_d.selected_node == local_d.selected_node
            assert remote_d.source is DecisionSource.LLM
            assert server.served == 1
        finally:
            client.close()

    def test_concurrent_requests_multiplex(self, server):
        client = ReplicaClient("127.0.0.1", server.port)
        try:
            nodes = make_nodes()
            with ThreadPoolExecutor(8) as pool:
                futs = [
                    pool.submit(client.get_scheduling_decision, make_pod(i), nodes)
                    for i in range(16)
                ]
                decisions = [f.result(timeout=30) for f in futs]
            assert len(decisions) == 16
            assert server.served == 16
        finally:
            client.close()

    def test_infeasible_propagates_as_infeasible(self, server):
        client = ReplicaClient("127.0.0.1", server.port)
        try:
            pod = PodSpec(
                name="huge", namespace="default", cpu_request=999.0,
                memory_request=999.0,
            )
            with pytest.raises(NoFeasibleNodeError):
                client.get_scheduling_decision(pod, make_nodes())
        finally:
            client.close()

    def test_backend_error_propagates(self):
        stub = StubBackend()
        stub.fail_next = 1
        srv = ReplicaServer(stub, host="127.0.0.1", port=0)
        client = ReplicaClient("127.0.0.1", srv.port)
        try:
            with pytest.raises(BackendError):
                client.get_scheduling_decision(make_pod(), make_nodes())
            # next call succeeds — the connection survives a backend error
            d = client.get_scheduling_decision(make_pod(), make_nodes())
            assert d.selected_node.startswith("node-")
        finally:
            client.close()
            srv.close()

    def test_overload_fails_fast_not_queues(self):
        """Requests beyond max_inflight get an immediate 'overloaded'
        backend error instead of queueing unbounded (advisor r4: a peer
        must not grow server memory/threads without bound)."""
        stub = StubBackend(latency_s=0.4)
        srv = ReplicaServer(stub, host="127.0.0.1", port=0, max_inflight=1)
        client = ReplicaClient("127.0.0.1", srv.port)
        try:
            nodes = make_nodes()
            with ThreadPoolExecutor(4) as pool:
                futs = [
                    pool.submit(client.get_scheduling_decision, make_pod(i), nodes)
                    for i in range(4)
                ]
                results = []
                for f in futs:
                    try:
                        results.append(("ok", f.result(timeout=30)))
                    except BackendError as exc:
                        results.append(("err", str(exc)))
            oks = [r for r in results if r[0] == "ok"]
            errs = [r for r in results if r[0] == "err"]
            assert oks, results  # at least the admitted request completes
            assert errs and all("overloaded" in e for _, e in errs), results
        finally:
            client.close()
            srv.close()

    def test_connection_cap_rejects_excess_dials(self):
        """Beyond max_connections, new connections are closed at accept —
        each live connection costs a reader thread, so the cap bounds what
        a dial-in-a-loop peer can allocate."""
        srv = ReplicaServer(StubBackend(), host="127.0.0.1", port=0,
                            max_connections=1)
        c1 = ReplicaClient("127.0.0.1", srv.port)
        c2 = ReplicaClient("127.0.0.1", srv.port, request_timeout_s=2)
        try:
            d = c1.get_scheduling_decision(make_pod(), make_nodes())
            assert d.selected_node.startswith("node-")
            with pytest.raises(BackendError):
                c2.get_scheduling_decision(make_pod(), make_nodes())
            # first connection unaffected by the rejected dial
            d = c1.get_scheduling_decision(make_pod(1), make_nodes())
            assert d.selected_node.startswith("node-")
        finally:
            c1.close()
            c2.close()
            srv.close()

    def test_link_drop_fails_inflight_requests(self):
        import socket as socket_mod

        stub = StubBackend(latency_s=0.5)
        srv = ReplicaServer(stub, host="127.0.0.1", port=0)
        client = ReplicaClient("127.0.0.1", srv.port)
        try:
            with ThreadPoolExecutor(2) as pool:
                fut = pool.submit(
                    client.get_scheduling_decision, make_pod(), make_nodes()
                )
                time.sleep(0.1)
                # simulate the link dropping mid-request (shutdown, not
                # close: close from another thread does not interrupt a
                # blocked recv)
                client._sock.shutdown(socket_mod.SHUT_RDWR)
                with pytest.raises(BackendError):
                    fut.result(timeout=10)
        finally:
            client.close()
            srv.close()
            srv._pool.shutdown(wait=True)  # the running 0.5 s decide, as below


class TestPrewarmOverWire:
    def test_prewarm_forwards_and_resolves(self):
        from concurrent.futures import Future

        stub = StubBackend()
        seen: list[int] = []

        def prewarm_prefix(nodes):
            seen.append(len(nodes))
            f: Future = Future()
            f.set_result(True)
            return f

        stub.prewarm_prefix = prewarm_prefix
        srv = ReplicaServer(stub, host="127.0.0.1", port=0)
        client = ReplicaClient("127.0.0.1", srv.port)
        try:
            assert client.prewarm_prefix(make_nodes(3)).result(timeout=5) is True
            assert seen == [3]
            # node metrics survive the wire: the worker prewarms the SAME
            # snapshot the coordinator rendered
        finally:
            client.close()
            srv.close()

    def test_prewarm_unsupported_backend_answers_false(self):
        srv = ReplicaServer(StubBackend(), host="127.0.0.1", port=0)
        client = ReplicaClient("127.0.0.1", srv.port)
        try:
            assert client.prewarm_prefix(make_nodes(2)).result(timeout=5) is False
        finally:
            client.close()
            srv.close()

    def test_prewarm_unanswered_expires_as_transport_failure(self):
        """A worker that accepts the frame but never replies must not wedge
        the future forever — the request deadline raises BackendError (a
        transport failure, which FanoutBackend's health gating cools)."""
        from concurrent.futures import Future

        stub = StubBackend()
        stub.prewarm_prefix = lambda nodes: Future()  # never resolves
        srv = ReplicaServer(stub, host="127.0.0.1", port=0)
        client = ReplicaClient("127.0.0.1", srv.port, request_timeout_s=0.3)
        try:
            with pytest.raises(BackendError):
                client.prewarm_prefix(make_nodes(2)).result(timeout=5)
        finally:
            client.close()
            srv.close()

    def test_prewarm_unreachable_raises_transport_failure(self):
        client = ReplicaClient("127.0.0.1", 1, connect_timeout_s=0.2)
        try:
            with pytest.raises(BackendError):
                client.prewarm_prefix(make_nodes(2)).result(timeout=5)
        finally:
            client.close()

    def test_fanout_aggregates_all_replicas(self):
        from concurrent.futures import Future
        from k8s_llm_scheduler_tpu.sched.replica import FanoutBackend

        class Warmable(StubBackend):
            def __init__(self, ok):
                super().__init__()
                self.ok = ok
                self.warmed = 0

            def prewarm_prefix(self, nodes):
                self.warmed += 1
                f: Future = Future()
                f.set_result(self.ok)
                return f

        a, b = Warmable(True), Warmable(True)
        fo = FanoutBackend([a, b])
        assert fo.prewarm_prefix(make_nodes(2)).result(timeout=5) is True
        assert (a.warmed, b.warmed) == (1, 1)
        # one dropped install surfaces as False (re-arms the loop's retry)
        # but is a HEALTHY answer: no cooldown
        b.ok = False
        assert fo.prewarm_prefix(make_nodes(2)).result(timeout=5) is False
        assert fo._health[1].cooldown_until == 0.0
        # no replica supports it -> None (prewarm loop disables)
        assert FanoutBackend([StubBackend()]).prewarm_prefix(make_nodes(2)) is None

    def test_fanout_transport_failure_cools_replica(self):
        """A replica whose prewarm RAISES (dead host) enters the same
        exponential cooldown decisions use; subsequent prewarms skip it
        (no blocking dial per tick) until the cooldown expires."""
        from concurrent.futures import Future
        from k8s_llm_scheduler_tpu.sched.replica import FanoutBackend

        class Dead(StubBackend):
            def __init__(self):
                super().__init__()
                self.dials = 0

            def prewarm_prefix(self, nodes):
                self.dials += 1
                f: Future = Future()
                f.set_exception(BackendError("black hole"))
                return f

        class Good(StubBackend):
            def prewarm_prefix(self, nodes):
                f: Future = Future()
                f.set_result(True)
                return f

        dead, good = Dead(), Good()
        fo = FanoutBackend([good, dead])
        assert fo.prewarm_prefix(make_nodes(2)).result(timeout=5) is False
        assert dead.dials == 1
        assert fo._health[1].cooldown_until > 0
        # cooling: the dead replica is NOT dialed again; healthy one is
        assert fo.prewarm_prefix(make_nodes(2)).result(timeout=5) is True
        assert dead.dials == 1


class TestConnectionLifecycle:
    def test_unreachable_replica_fails_fast_then_heals(self):
        """Constructing a client to a not-yet-up worker must not raise
        (the coordinator starts before workers finish loading weights);
        decisions fail fast as BackendError until the worker appears,
        then succeed without any reconnect ceremony."""
        import socket as socket_mod

        with socket_mod.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        client = ReplicaClient("127.0.0.1", port, connect_timeout_s=0.5)
        try:
            with pytest.raises(BackendError, match="unreachable"):
                client.get_scheduling_decision(make_pod(), make_nodes())
            srv = ReplicaServer(StubBackend(), host="127.0.0.1", port=port)
            try:
                d = client.get_scheduling_decision(make_pod(), make_nodes())
                assert d.selected_node.startswith("node-")
            finally:
                srv.close()
        finally:
            client.close()

    def test_reconnects_after_worker_restart(self):
        """A worker restart must not permanently disable its replica slot:
        the in-flight request fails, and later submits re-dial the fresh
        server."""
        srv1 = ReplicaServer(StubBackend(), host="127.0.0.1", port=0)
        port = srv1.port
        client = ReplicaClient("127.0.0.1", port)
        try:
            assert client.get_scheduling_decision(
                make_pod(), make_nodes()
            ).selected_node.startswith("node-")
            srv1.close()  # worker dies
            time.sleep(0.1)
            # restart on the same port
            srv2 = ReplicaServer(StubBackend(), host="127.0.0.1", port=port)
            try:
                deadline = time.monotonic() + 10
                last = None
                while time.monotonic() < deadline:
                    try:
                        d = client.get_scheduling_decision(
                            make_pod(), make_nodes()
                        )
                        break
                    except BackendError as exc:
                        last = exc
                        time.sleep(0.05)
                else:
                    pytest.fail(f"never healed: {last}")
                assert d.selected_node.startswith("node-")
                assert srv2.served >= 1
            finally:
                srv2.close()
        finally:
            client.close()


class TestReconnectBackoff:
    def _free_port(self) -> int:
        import socket as socket_mod

        with socket_mod.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def test_repeated_dial_failures_open_failfast_window(self):
        """The first failed dial keeps the historical immediate-retry
        contract; from the SECOND consecutive failure on, submits fail
        fast inside a jittered exponential window instead of paying a
        blocking connect each (a restarting worker must not eat one
        connect_timeout_s stall per in-flight decision)."""
        port = self._free_port()
        client = ReplicaClient(
            "127.0.0.1", port, connect_timeout_s=0.5,
            reconnect_base_s=5.0, reconnect_cap_s=30.0,
        )
        try:
            # failures 1 and 2 both really dial (window opens on #2)
            for _ in range(2):
                with pytest.raises(BackendError, match="unreachable"):
                    client.get_scheduling_decision(make_pod(), make_nodes())
            assert client._dial_failures == 2
            # inside the window: immediate failure, no dial attempt
            t0 = time.monotonic()
            with pytest.raises(BackendError, match="backing off"):
                client.get_scheduling_decision(make_pod(), make_nodes())
            assert time.monotonic() - t0 < 0.2
            assert client._dial_failures == 2  # fail-fast is not a dial
        finally:
            client.close()

    def test_restart_under_inflight_decisions_heals(self):
        """Kill and restart a ReplicaServer UNDER in-flight decisions:
        every in-flight call resolves (decision or BackendError — no
        hangs), and after the restart the same client heals through the
        backoff and serves again."""
        backend = StubBackend(latency_s=0.15)
        srv1 = ReplicaServer(backend, host="127.0.0.1", port=0)
        port = srv1.port
        client = ReplicaClient(
            "127.0.0.1", port,
            reconnect_base_s=0.05, reconnect_cap_s=0.2,
        )
        srv2 = None
        try:
            # warm the connection so the kill lands mid-stream
            client.get_scheduling_decision(make_pod(), make_nodes())

            with ThreadPoolExecutor(max_workers=8) as pool:
                futs = [
                    pool.submit(
                        client.get_scheduling_decision,
                        make_pod(i), make_nodes(),
                    )
                    for i in range(8)
                ]
                time.sleep(0.05)   # decisions are in flight (0.15s each)
                srv1.close()       # worker dies mid-stream
                outcomes = []
                for fut in futs:
                    try:
                        outcomes.append(fut.result(timeout=10))
                    except BackendError as exc:
                        outcomes.append(exc)
            # nothing hung; the kill surfaced as BackendError for the
            # requests it caught in flight
            assert len(outcomes) == 8
            assert any(isinstance(o, BackendError) for o in outcomes)

            # restart on the same port; the client heals through the
            # jittered backoff without being rebuilt
            srv2 = ReplicaServer(StubBackend(), host="127.0.0.1", port=port)
            deadline = time.monotonic() + 10
            last = None
            while time.monotonic() < deadline:
                try:
                    d = client.get_scheduling_decision(
                        make_pod(), make_nodes()
                    )
                    break
                except BackendError as exc:
                    last = exc
                    time.sleep(0.05)
            else:
                pytest.fail(f"never healed: {last}")
            assert d.selected_node.startswith("node-")
            assert srv2.served >= 1
            assert client._dial_failures == 0  # reset on success
        finally:
            client.close()
            srv1.close()
            if srv2 is not None:
                srv2.close()


class TestZeroCopyFraming:
    def test_vectored_send_handles_partial_writes(self):
        """_send_frames must reassemble correctly when the kernel accepts
        arbitrary partial iovec spans (short sendmsg returns that split a
        header, a payload, and a frame boundary)."""
        from k8s_llm_scheduler_tpu.sched.replica import (
            _encode_frame,
            _send_frames,
        )

        class ChunkySock:
            """sendmsg accepts at most `cap` bytes per call."""

            def __init__(self, cap):
                self.cap = cap
                self.data = bytearray()

            def sendmsg(self, bufs):
                take = self.cap
                n = 0
                for b in bufs:
                    piece = bytes(b[:take])
                    self.data.extend(piece)
                    n += len(piece)
                    take -= len(piece)
                    if take <= 0:
                        break
                return n

        objs = [{"id": i, "payload": "x" * (7 * i + 3)} for i in range(5)]
        for cap in (1, 2, 3, 5, 64, 4096):
            sock = ChunkySock(cap)
            _send_frames(sock, [_encode_frame(o) for o in objs])
            # decode the byte stream back into frames
            import json as _json
            import struct as _struct

            buf = bytes(sock.data)
            decoded = []
            while buf:
                (length,) = _struct.unpack(">I", buf[:4])
                decoded.append(_json.loads(buf[4:4 + length].decode()))
                buf = buf[4 + length:]
            assert decoded == objs, f"cap={cap}"


class TestBatchedFlush:
    def test_concurrent_frames_share_one_socket_and_flush(self, server):
        """Batched decision-frame flushing: a burst of concurrent
        decisions rides ONE persistent socket (dials == 1 across the
        whole burst) and every frame reaches the wire (frames_sent
        exact); flushes never exceed frames (coalescing can only merge
        syscalls, not add them)."""
        client = ReplicaClient("127.0.0.1", server.port)
        try:
            nodes = make_nodes()
            with ThreadPoolExecutor(12) as pool:
                futs = [
                    pool.submit(
                        client.get_scheduling_decision, make_pod(i), nodes
                    )
                    for i in range(24)
                ]
                decisions = [f.result(timeout=30) for f in futs]
            assert len(decisions) == 24
            w = client.wire_stats()
            assert w["dials"] == 1
            assert w["frames_sent"] == 24
            assert 1 <= w["flushes"] <= w["frames_sent"]
            assert w["bytes_sent"] > 0
            assert w["max_batch"] >= 1
        finally:
            client.close()

    def test_send_failure_fails_batchmates_not_hangs(self, server):
        """A frame whose flush hits a dead socket must resolve every
        batchmate with BackendError (no caller may hang out its full
        request timeout)."""
        client = ReplicaClient("127.0.0.1", server.port, request_timeout_s=5.0)
        try:
            client.get_scheduling_decision(make_pod(), make_nodes())  # dial
            server.close()  # peer gone; next sends hit a dead socket
            t0 = time.monotonic()
            with ThreadPoolExecutor(4) as pool:
                futs = [
                    pool.submit(
                        client.get_scheduling_decision,
                        make_pod(i), make_nodes(),
                    )
                    for i in range(4)
                ]
                outcomes = []
                for fut in futs:
                    try:
                        outcomes.append(fut.result(timeout=10))
                    except BackendError as exc:
                        outcomes.append(exc)
            assert all(isinstance(o, BackendError) for o in outcomes)
            assert time.monotonic() - t0 < 5.0  # nobody waited out 5s
        finally:
            client.close()


class TestPersistentReuseUnderRecovery:
    def test_kill_restart_reuses_persistent_socket(self):
        """Connection-reuse keepalive under recovery (the fused decision
        plane's dispatch transport): kill and restart the worker under
        in-flight decisions — after recovery, EVERY subsequent decision
        frame reuses one persistent socket (exactly one re-dial, no
        per-frame reconnect/handshake), and the first-failure
        immediate-retry contract holds (a single failed dial opens no
        backoff window)."""
        backend = StubBackend(latency_s=0.1)
        srv1 = ReplicaServer(backend, host="127.0.0.1", port=0)
        port = srv1.port
        client = ReplicaClient(
            "127.0.0.1", port,
            reconnect_base_s=0.05, reconnect_cap_s=0.2,
        )
        srv2 = None
        try:
            client.get_scheduling_decision(make_pod(), make_nodes())
            assert client.wire_stats()["dials"] == 1

            with ThreadPoolExecutor(max_workers=4) as pool:
                futs = [
                    pool.submit(
                        client.get_scheduling_decision,
                        make_pod(i), make_nodes(),
                    )
                    for i in range(4)
                ]
                time.sleep(0.03)
                srv1.close()  # kill under in-flight decisions
                for fut in futs:
                    try:
                        fut.result(timeout=10)
                    except BackendError:
                        pass  # in-flight failures are the expected shape

            # First-failure immediate retry: with the server still down,
            # ONE failed dial must not open a fail-fast window...
            with pytest.raises(BackendError):
                client.get_scheduling_decision(make_pod(), make_nodes())
            assert client._dial_failures >= 1
            # ...so the very next attempt AFTER the worker rebinds its
            # socket succeeds without waiting out any backoff (the
            # "backing off" error shape must not appear once the peer
            # is up, if only one dial had failed).
            srv2 = ReplicaServer(StubBackend(), host="127.0.0.1", port=port)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                try:
                    client.get_scheduling_decision(make_pod(), make_nodes())
                    break
                except BackendError:
                    time.sleep(0.02)
            else:
                pytest.fail("never healed after restart")

            dials_after_heal = client.wire_stats()["dials"]
            # Post-recovery decisions all reuse the healed socket: the
            # dial counter must not move again.
            with ThreadPoolExecutor(4) as pool:
                futs = [
                    pool.submit(
                        client.get_scheduling_decision,
                        make_pod(i), make_nodes(),
                    )
                    for i in range(8)
                ]
                for fut in futs:
                    fut.result(timeout=30)
            w = client.wire_stats()
            assert w["dials"] == dials_after_heal
            assert w["frames_sent"] >= 8
        finally:
            client.close()
            srv1.close()
            if srv2 is not None:
                srv2.close()


class TestAsyncPath:
    async def test_async_decision_and_fanout(self, server):
        """The natively-async client path resolves without a worker
        thread, and FanoutBackend exposes it (hiding it would throttle
        leaders through the to_thread pool)."""
        client = ReplicaClient("127.0.0.1", server.port)
        local = StubBackend()
        fan = FanoutBackend([local, client])
        try:
            import asyncio

            nodes = make_nodes()
            decisions = await asyncio.gather(*[
                fan.get_scheduling_decision_async(make_pod(i), nodes)
                for i in range(8)
            ])
            assert len(decisions) == 8
            # health-aware dispatch: both replicas participate under
            # concurrency (exact split depends on observed latencies)
            assert all(n > 0 for n in fan.routed), fan.routed
            assert sum(fan.routed) == 8
            assert server.served == fan.routed[1]
        finally:
            client.close()

    def test_timeout_raises_backend_error_and_drops_pending(self):
        stub = StubBackend(latency_s=1.0)
        srv = ReplicaServer(stub, host="127.0.0.1", port=0)
        client = ReplicaClient(
            "127.0.0.1", srv.port, request_timeout_s=0.15
        )
        try:
            with pytest.raises(BackendError, match="timed out"):
                client.get_scheduling_decision(make_pod(), make_nodes())
            # the pending-table entry must not leak for the connection's
            # lifetime
            assert client._pending == {}
        finally:
            client.close()
            srv.close()


class TestFanout:
    def test_dispatch_over_local_and_remote(self, server):
        client = ReplicaClient("127.0.0.1", server.port)
        local = StubBackend()
        fan = FanoutBackend([local, client])
        try:
            nodes = make_nodes()
            for i in range(6):
                d = fan.get_scheduling_decision(make_pod(i), nodes)
                assert d.selected_node.startswith("node-")
            # health-aware dispatch starts both replicas (unknown latency
            # ranks optimistic + rotation tiebreak), then PREFERS the
            # faster local stub — the slower remote must not get an
            # equal share (that was round-robin's tail problem)
            assert sum(fan.routed) == 6
            assert all(n > 0 for n in fan.routed), fan.routed
            assert fan.routed[0] >= fan.routed[1], fan.routed
            assert local.calls == fan.routed[0]
            assert server.served == fan.routed[1]
            assert fan.get_stats()["fanout_routed"] == fan.routed
        finally:
            client.close()

    def test_empty_replicas_rejected(self):
        with pytest.raises(ValueError):
            FanoutBackend([])


class TestHealthAwareDispatch:
    def _run_burst(self, fan, n=48, pool_size=8):
        nodes = make_nodes()
        start = time.perf_counter()
        with ThreadPoolExecutor(pool_size) as pool:
            futs = [
                pool.submit(fan.get_scheduling_decision, make_pod(i), nodes)
                for i in range(n)
            ]
            for f in futs:
                f.result(timeout=60)
        return time.perf_counter() - start

    def test_slow_replica_degrades_throughput_under_20pct(self):
        """VERDICT r4 item 7 done-criterion: a 10x-slower replica must
        cost < 20% throughput (round-robin cost ~50%: half of every burst
        queued behind the slow host). Weighted least-load dispatch keeps
        the slow replica at roughly its fair service-rate share.

        A short untimed warmup primes the latency EMAs first: the very
        first dispatches legitimately PROBE the unknown replica (how its
        latency gets learned at all), and on a burst this small those
        probes' 0.2 s tails would swamp the steady-state measurement."""
        fan_fast = FanoutBackend([StubBackend(latency_s=0.02),
                                  StubBackend(latency_s=0.02)])
        fan = FanoutBackend([StubBackend(latency_s=0.02),
                             StubBackend(latency_s=0.2)])
        self._run_burst(fan_fast, n=8)  # warmup: prime EMAs
        self._run_burst(fan, n=8)
        routed_before = list(fan.routed)
        # fastest of three bursts an arm, in turn: a burst lasts ~0.15 s,
        # and on a loaded host (tier 1 runs six workers) one descheduled
        # pool thread is worth 20% of that — it read "29% degraded" with
        # all 48 pods routed to the fast replica (PR 26). A policy that
        # queues pods behind the slow host is slow in every burst.
        wall_fast = wall_mixed = float("inf")
        for _ in range(3):
            wall_fast = min(wall_fast, self._run_burst(fan_fast))
            wall_mixed = min(wall_mixed, self._run_burst(fan))
        timed_routing = [a - b for a, b in zip(fan.routed, routed_before)]
        # routing skew is the mechanism: the fast replica carries (nearly)
        # the whole steady-state burst
        assert timed_routing[0] >= 5 * max(1, timed_routing[1]), fan.routed
        degradation = wall_mixed / wall_fast - 1.0
        assert degradation < 0.20, (
            f"10x-slow replica degraded throughput {degradation:.0%} "
            f"(routed {timed_routing})"
        )

    def test_one_slow_sample_does_not_starve_forever(self):
        """A transiently-slow replica (one 'cold compile' sample) must be
        re-probed after PROBE_IDLE_S and recover its share — the EMA only
        updates on routed requests, so without probing it would be
        starved permanently.

        Deflaked (VERDICT r5 #6): dispatch health reads an INJECTED clock
        that the test advances explicitly, so probe-window expiry, EMA
        samples, and the probe's count gate are exact — no real sleeps
        racing a loaded host's scheduler."""

        class _FakeClock:
            def __init__(self) -> None:
                self.t = 1000.0

            def now(self) -> float:
                return self.t

            def advance(self, dt: float) -> None:
                self.t += dt

        class _ClockedStub(StubBackend):
            """Simulated latency: advances the fan-out's clock instead of
            sleeping, so FanoutBackend's elapsed = clock()-start sees it."""

            def __init__(self, clock: "_FakeClock", latency_s: float) -> None:
                super().__init__()
                self.clock = clock
                self.sim_latency_s = latency_s

            def get_scheduling_decision(self, pod, nodes):
                self.clock.advance(self.sim_latency_s)
                return super().get_scheduling_decision(pod, nodes)

        clock = _FakeClock()
        transient = _ClockedStub(clock, latency_s=0.3)  # first sample: slow
        fast = _ClockedStub(clock, latency_s=0.01)
        fan = FanoutBackend([transient, fast], clock=clock.now)
        fan.PROBE_IDLE_S = 0.2  # test-speed probe window
        nodes = make_nodes()
        fan.get_scheduling_decision(make_pod(0), nodes)  # slow sample
        transient.sim_latency_s = 0.01  # transient condition over
        clock.advance(0.25)  # idle past the probe window — no wall sleep
        for i in range(1, 13):
            fan.get_scheduling_decision(make_pod(i), nodes)
        # the probe re-sampled it; with matched latencies it shares again
        assert fan.routed[0] >= 3, fan.routed
        assert fan.routed[1] >= 3, fan.routed

    def test_failing_replica_enters_cooldown_and_recovers(self):
        fast = StubBackend()
        flaky = StubBackend()
        flaky.fail_next = 3
        fan = FanoutBackend([flaky, fast])
        nodes = make_nodes()
        # first dispatch goes to the flaky replica (rotation tiebreak),
        # fails, and puts it in cooldown
        with pytest.raises(BackendError):
            fan.get_scheduling_decision(make_pod(0), nodes)
        for i in range(1, 6):
            d = fan.get_scheduling_decision(make_pod(i), nodes)
            assert d.selected_node.startswith("node-")
        assert fan.routed[1] >= 5  # cooldown kept traffic off the failure
        assert fan.get_stats()["fanout_cooling"][0] is True
        # after the cooldown expires the replica rejoins and heals
        time.sleep(0.55)
        flaky.fail_next = 0
        before = fan.routed[0]
        for i in range(6, 10):
            fan.get_scheduling_decision(make_pod(i), nodes)
        assert fan.routed[0] > before, fan.routed


class TestFanoutSchedulerE2E:
    """The full control loop over a fanned-out backend: a burst schedules
    across local + remote replicas, and a replica dying MID-BURST degrades
    through the retry/fallback stack instead of losing pods — the chaos
    contract the single-backend path already guarantees (test_chaos)."""

    async def _run_burst(self, fan, n_pods, cluster):
        import asyncio

        from k8s_llm_scheduler_tpu.core.breaker import CircuitBreaker
        from k8s_llm_scheduler_tpu.core.cache import DecisionCache
        from k8s_llm_scheduler_tpu.sched.client import DecisionClient
        from k8s_llm_scheduler_tpu.sched.loop import Scheduler
        from k8s_llm_scheduler_tpu.testing import SCHEDULER_NAME, pod_burst

        client = DecisionClient(
            fan, cache=DecisionCache(), breaker=CircuitBreaker(),
            retry_delay=0.01,
        )
        sched = Scheduler(
            cluster, cluster, client, scheduler_name=SCHEDULER_NAME,
            snapshot_ttl_s=300.0,
        )
        task = asyncio.create_task(sched.run())
        pods = pod_burst(n_pods, distinct_shapes=8)
        for p in pods:
            cluster.add_pod(p)
        async with async_deadline(60):
            while cluster.bind_count < n_pods:
                await asyncio.sleep(0.01)
        sched.stop()
        await asyncio.wait_for(task, timeout=30)
        return sched.get_stats()

    async def test_burst_schedules_across_replicas(self):
        from k8s_llm_scheduler_tpu.testing import synthetic_cluster

        srv = ReplicaServer(StubBackend(), host="127.0.0.1", port=0)
        client = ReplicaClient("127.0.0.1", srv.port)
        local = StubBackend()
        fan = FanoutBackend([local, client])
        cluster = synthetic_cluster(4)
        try:
            stats = await self._run_burst(fan, 24, cluster)
            assert stats["total_scheduled"] == 24
            assert stats["fallback_decisions"] == 0
            # leaders actually split across BOTH replicas
            assert all(n > 0 for n in fan.routed), fan.routed
            assert srv.served > 0 and local.calls > 0
        finally:
            cluster.close()
            client.close()
            srv.close()

    async def test_replica_death_mid_burst_degrades_not_loses(self):
        import asyncio
        import socket as socket_mod

        from k8s_llm_scheduler_tpu.testing import synthetic_cluster

        # slow remote so its leaders are provably IN FLIGHT when the link
        # dies (an early fixed-delay kill landed after the whole burst had
        # bound and proved nothing)
        srv = ReplicaServer(StubBackend(latency_s=0.5), host="127.0.0.1", port=0)
        client = ReplicaClient("127.0.0.1", srv.port)
        local = StubBackend()
        fan = FanoutBackend([local, client])
        cluster = synthetic_cluster(4)
        # Witness that the failure path executed: count every BackendError
        # the remote replica surfaces. (The reconnect-capable client can
        # fully recover within the retry budget, leaving no trace in the
        # aggregate client stats — failed_requests counts only
        # retry-EXHAUSTED calls.)
        remote_errors: list[BackendError] = []
        orig_async = client.get_scheduling_decision_async

        async def counting_async(pod, nodes):
            try:
                return await orig_async(pod, nodes)
            except BackendError as exc:
                remote_errors.append(exc)
                raise

        client.get_scheduling_decision_async = counting_async
        orig_sync = client.get_scheduling_decision

        def counting_sync(pod, nodes):
            try:
                return orig_sync(pod, nodes)
            except BackendError as exc:
                remote_errors.append(exc)
                raise

        client.get_scheduling_decision = counting_sync
        try:
            killed_with_inflight = asyncio.Event()

            async def killer():
                # fire only once remote requests are actually outstanding
                async with async_deadline(30):
                    while not client._pending:
                        await asyncio.sleep(0.005)
                try:
                    client._sock.shutdown(socket_mod.SHUT_RDWR)
                finally:
                    killed_with_inflight.set()

            kill_task = asyncio.ensure_future(killer())
            stats = await self._run_burst(fan, 24, cluster)
            await kill_task
            assert killed_with_inflight.is_set()
            # EVERY pod got placed: the in-flight remote leaders surfaced
            # as BackendError and the retry (other replica via
            # round-robin, or the reconnected remote) or fallback stack
            # absorbed them
            assert stats["total_scheduled"] == 24
            assert (
                stats["llm_decisions"]
                + stats["cache_decisions"]
                + stats["fallback_decisions"]
                == 24
            )
            # the failure path genuinely ran
            assert remote_errors, "kill produced no BackendError"
        finally:
            cluster.close()
            client.close()
            srv.close()
            # decides already running outlive close() by up to the stub's
            # 0.5 s; left alone they record `replica.decide` traces into
            # whatever flight recorder the NEXT test installed
            srv._pool.shutdown(wait=True)
