"""Decision flight recorder, span tracing, histogram telemetry.

Covers the observability round end to end at the fast tier: span tree
mechanics + the flight recorder ring, cross-thread and cross-process
(replica wire) span propagation, PhaseRecorder histogram buckets and the
Prometheus `histogram` exposition families, label-value escaping, the
/debug endpoints on MetricsServer, and the background engine sampler. The
real-engine trace (prefill/decode token counts from an actual wave) lives
in the slow tier alongside the other jit-compiling e2e tests.
"""

import asyncio
import json
import re
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from k8s_llm_scheduler_tpu.core.cache import DecisionCache
from k8s_llm_scheduler_tpu.engine.backend import StubBackend
from k8s_llm_scheduler_tpu.observability import spans
from k8s_llm_scheduler_tpu.observability.metrics import (
    MetricsServer,
    render_prometheus,
)
from k8s_llm_scheduler_tpu.observability.sampler import EngineSampler
from k8s_llm_scheduler_tpu.observability.trace import (
    BUCKET_BOUNDS_S,
    PhaseRecorder,
    delta_hist,
    hist_percentiles,
)
from k8s_llm_scheduler_tpu.sched.client import DecisionClient
from k8s_llm_scheduler_tpu.sched.loop import Scheduler
from k8s_llm_scheduler_tpu.testing import (
    SCHEDULER_NAME,
    async_deadline,
    fixture_pods,
    synthetic_cluster,
)


@pytest.fixture()
def recorder():
    """Isolated flight recorder installed as the global ring (scheduler
    integration records there); restored after the test."""
    old = spans.flight
    spans.flight = rec = spans.FlightRecorder(capacity=64)
    spans.configure(enabled=True)
    yield rec
    spans.flight = old


# ---------------------------------------------------------------- span core
class TestSpans:
    def test_span_tree_nesting(self, recorder):
        with spans.start_trace("decision", pod="ns/p") as trace:
            with spans.span("decide", attempt=0):
                with spans.span("backend"):
                    pass
            with spans.span("bind"):
                pass
        tree = trace.span_tree()
        assert tree["name"] == "decision"
        kids = [c["name"] for c in tree["children"]]
        assert kids == ["decide", "bind"]
        decide = tree["children"][0]
        assert [c["name"] for c in decide["children"]] == ["backend"]
        assert decide["attrs"]["attempt"] == 0
        # serialized attrs are a COPY, never an alias of the live dict: a
        # producer mutating span attrs after the ring recorded the trace
        # must not reach (or race) an already-serialized entry
        live_decide = next(s for s in trace.spans if s.name == "decide")
        live_decide.attrs["attempt"] = 99
        assert decide["attrs"]["attempt"] == 0
        assert trace.root.dur_ms is not None
        # every child's wall time fits inside the root's
        assert sum(
            c["dur_ms"] for c in tree["children"]
        ) <= trace.root.dur_ms + 1e-6

    def test_error_status_and_publication(self, recorder):
        with pytest.raises(ValueError):
            with spans.start_trace("decision") as trace:
                with pytest.raises(ValueError):
                    with spans.span("decide"):
                        raise ValueError("inner")
                raise ValueError("outer")
        assert trace.root.status == "error"
        assert trace.spans[1].status == "error"
        # the failed trace still published — failures are exactly what the
        # flight recorder exists to explain
        assert recorder.get(trace.trace_id) is not None

    def test_backdated_root_covers_prior_interval(self, recorder):
        """The fast/follower paths open their trace AFTER the decision
        resolved; start_unix/start_perf backdate the root so its duration
        covers decide + bind, not just the bind."""
        t0_wall = time.time() - 0.2
        t0_perf = time.perf_counter() - 0.2
        with spans.start_trace(
            "decision", path="fast", start_unix=t0_wall, start_perf=t0_perf,
        ) as trace:
            trace.add_span("decide", start_unix=t0_wall, dur_ms=200.0)
        assert trace.root.start_unix == t0_wall
        assert trace.root.dur_ms >= 200.0
        # child no longer starts before its parent
        decide = next(s for s in trace.spans if s.name == "decide")
        assert decide.start_unix >= trace.root.start_unix

    def test_disabled_tracing_is_noop(self, recorder):
        spans.configure(enabled=False)
        try:
            with spans.start_trace("decision") as trace:
                assert trace is None
                with spans.span("decide") as sp:
                    assert sp is None
                assert spans.context() is None
                assert spans.capture() is None
                assert spans.wire_context() is None
            assert recorder.list() == []
        finally:
            spans.configure(enabled=True)

    def test_retroactive_add_span_and_capture(self, recorder):
        """The engine-worker shape: capture on one thread, attach
        retroactive spans from another."""
        with spans.start_trace("decision") as trace:
            cap = spans.capture()
            assert cap is not None
            captured_trace, ctx = cap
            assert captured_trace is trace
            assert ctx.trace_id == trace.trace_id

            def worker():
                captured_trace.add_span(
                    "admission_wait", start_unix=time.time() - 0.01,
                    dur_ms=10.0, parent_id=ctx.span_id,
                )

            t = threading.Thread(target=worker)
            t.start()
            t.join()
        names = [s.name for s in trace.spans]
        assert "admission_wait" in names
        sp = next(s for s in trace.spans if s.name == "admission_wait")
        assert sp.parent_id == trace.root.span_id
        assert sp.dur_ms == 10.0

    def test_merge_remote_spans_rejects_foreign_trace(self, recorder):
        with spans.start_trace("decision") as trace:
            good = {
                "name": "replica.decide", "trace_id": trace.trace_id,
                "span_id": "r-1", "parent_id": trace.root.span_id,
                "start_unix": time.time(), "dur_ms": 5.0, "attrs": {},
                "status": "ok",
            }
            foreign = dict(good, trace_id="someone-else", span_id="r-2")
            malformed = {"nope": True}
            merged = trace.merge_remote_spans([good, foreign, malformed])
        assert merged == 1
        assert [s for s in trace.spans if s.name == "replica.decide"]
        assert not [s for s in trace.spans if s.span_id == "r-2"]


class TestFlightRecorder:
    def test_ring_eviction_and_seq(self):
        rec = spans.FlightRecorder(capacity=3)
        ids = []
        for i in range(5):
            with spans.start_trace("decision", recorder=rec, i=i) as t:
                ids.append(t.trace_id)
        assert rec.seq == 5
        held = rec.list(n=10)
        assert len(held) == 3
        assert [e["trace_id"] for e in held] == ids[-3:]
        assert rec.get(ids[0]) is None  # evicted
        assert rec.get(ids[-1]) is not None
        # tail cursor: only entries after since_seq
        assert [e["seq"] for e in rec.list(n=10, since_seq=4)] == [5]

    def test_late_spans_refresh_recorded_entry(self):
        """Spans attached AFTER the root closed (a timed-out decision
        whose wave harvests later) must re-publish the ring entry — the
        serialized copy would otherwise hide the engine attribution for
        exactly the tail decisions the recorder exists to explain."""
        rec = spans.FlightRecorder(capacity=4)
        with spans.start_trace("decision", recorder=rec) as t:
            pass  # root closes, entry serialized into the ring
        before = rec.get(t.trace_id)
        assert {s["name"] for s in before["spans"]} == {"decision"}
        seq_before = before["seq"]
        t.add_span("admission_wait", start_unix=time.time(), dur_ms=5.0)
        t.merge_remote_spans([{
            "name": "replica.decide", "trace_id": t.trace_id,
            "span_id": "r-9", "parent_id": t.root.span_id,
            "start_unix": time.time(), "dur_ms": 3.0, "attrs": {},
            "status": "ok",
        }])
        after = rec.get(t.trace_id)
        assert {s["name"] for s in after["spans"]} == {
            "decision", "admission_wait", "replica.decide",
        }
        assert after["seq"] == seq_before  # refreshed in place, not re-added
        assert len(rec.list(10)) == 1

    def test_export_jsonl_roundtrip(self):
        rec = spans.FlightRecorder(capacity=8)
        with spans.start_trace("decision", recorder=rec) as t:
            with spans.span("decide"):
                pass
            t.meta["source"] = "llm"
        lines = rec.export_jsonl().strip().splitlines()
        assert len(lines) == 1
        entry = json.loads(lines[0])
        assert entry["trace_id"] == t.trace_id
        assert entry["meta"]["source"] == "llm"
        assert {s["name"] for s in entry["spans"]} == {"decision", "decide"}


# ------------------------------------------------------------- histograms
class TestPhaseHistograms:
    def test_bucket_counts_sum_to_count(self):
        rec = PhaseRecorder()
        values = [0.00005, 0.0002, 0.003, 0.01, 0.21, 5.0, 999.0]
        for v in values:
            rec.record("decide", v)
        snap = rec.snapshot()["decide"]
        hist = snap["_hist"]
        assert sum(hist["counts"]) == hist["count"] == len(values)
        assert hist["sum_s"] == pytest.approx(sum(values))
        # 999 s exceeds the last bound -> overflow bucket
        assert hist["counts"][-1] == 1

    def test_bucket_index_boundaries(self):
        # each recorded value must land in a bucket whose bound covers it
        rec = PhaseRecorder()
        for v in (1e-5, 1e-4, 2e-4, 3.3e-4, 0.0501, 1.0, 400.0):
            rec.record("p", v)
            counts = rec.snapshot()["p"]["_hist"]["counts"]
            idx = next(i for i, c in enumerate(counts) if c)
            if idx < len(BUCKET_BOUNDS_S):
                assert v <= BUCKET_BOUNDS_S[idx] * (1 + 1e-9)
            if idx > 0:
                # not absurdly over-bucketed: the bound below is < value
                assert BUCKET_BOUNDS_S[idx - 1] < v * (1 + 1e-9)
            rec.reset()

    def test_percentiles_are_monotone_and_conservative(self):
        rec = PhaseRecorder()
        for _ in range(50):
            rec.record("decide", 0.001)
        rec.record("decide", 1.0)  # one 1s outlier (rank > p99 of 51)
        snap = rec.snapshot()["decide"]
        assert snap["p50_ms"] <= snap["p95_ms"] <= snap["p99_ms"]
        # p50 sits in the ~1ms region, p99 must surface the outlier's bucket
        assert snap["p50_ms"] < 2.0
        assert snap["p99_ms"] >= 1000.0
        # conservative: percentile estimates never understate (upper bound)
        assert snap["p50_ms"] >= 1.0

    def test_delta_hist_isolates_window(self):
        rec = PhaseRecorder()
        rec.record("decide", 0.001)
        before = rec.snapshot()["decide"]
        for _ in range(10):
            rec.record("decide", 0.1)
        after = rec.snapshot()["decide"]
        dh = delta_hist(before, after)
        assert dh["count"] == 10
        assert dh["sum_s"] == pytest.approx(1.0)
        p50, _, _ = hist_percentiles(dh["counts"])
        assert 100.0 <= p50 <= 205.0  # window median ~100ms, not 1ms

    def test_snapshot_race_with_reset(self):
        """record() racing reset() must never divide by zero or corrupt a
        snapshot (the pre-round hazard: building the snapshot entry by
        entry while the dicts mutate under it)."""
        rec = PhaseRecorder()
        stop = threading.Event()
        errors = []

        def writer():
            while not stop.is_set():
                rec.record("decide", 0.001)
                rec.reset()

        def reader():
            try:
                while not stop.is_set():
                    for snap in rec.snapshot().values():
                        assert snap["count"] >= 1
                        assert snap["avg_ms"] >= 0.0
            except Exception as exc:  # pragma: no cover - failure path
                errors.append(exc)

        threads = [threading.Thread(target=writer) for _ in range(2)]
        threads.append(threading.Thread(target=reader))
        for t in threads:
            t.start()
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join()
        assert errors == []


class TestPrometheusHistograms:
    def test_histogram_families_valid(self):
        rec = PhaseRecorder()
        for v in (0.0002, 0.003, 0.01, 0.21, 5.0):
            rec.record("decide", v)
            rec.record("bind", v / 10)
        text = render_prometheus({"phases": rec.snapshot()})
        for family in (
            "llm_scheduler_phases_decide_seconds",
            "llm_scheduler_phases_bind_seconds",
        ):
            # exactly one TYPE histogram header per family
            assert text.count(f"# TYPE {family} histogram") == 1
            buckets = re.findall(
                rf'^{family}_bucket{{le="([^"]+)"}} (\d+)$',
                text, re.MULTILINE,
            )
            assert buckets, f"no buckets for {family}"
            # le-ordered and cumulative-monotone, ending at +Inf
            counts = [int(c) for _, c in buckets]
            assert counts == sorted(counts), "buckets not monotone"
            les = [le for le, _ in buckets]
            assert les[-1] == "+Inf"
            finite = [float(le) for le in les[:-1]]
            assert finite == sorted(finite)
            # +Inf bucket equals _count
            count = int(re.search(
                rf"^{family}_count (\d+)$", text, re.MULTILINE
            ).group(1))
            assert counts[-1] == count == 5
            # _sum present and plausible
            total = float(re.search(
                rf"^{family}_sum ([0-9.e+-]+)$", text, re.MULTILINE
            ).group(1))
            assert total > 0
        # derived percentile gauges ride alongside
        assert "llm_scheduler_phases_decide_p99_ms" in text

    def test_gauge_and_histogram_families_do_not_collide(self):
        """The _hist payload must not leak into the gauge flattening."""
        rec = PhaseRecorder()
        rec.record("decide", 0.01)
        text = render_prometheus({"phases": rec.snapshot()})
        assert "_hist" not in text
        assert "counts" not in text

    def test_label_value_escaping(self):
        """A string stat containing quote/backslash/newline must render as
        VALID exposition text (Prometheus spec escaping), not break the
        line format."""
        stats = {
            "breaker": {"state": 'clo"sed'},
            "node": {"name": "has\\slash"},
            "msg": {"text": "two\nlines"},
        }
        text = render_prometheus(stats)
        assert 'state{value="clo\\"sed"}' in text
        assert 'name{value="has\\\\slash"}' in text
        assert 'text{value="two\\nlines"}' in text
        # no raw newline inside any sample line
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            assert re.match(
                r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}\n]*\})? [^ \n]+$', line
            ), f"malformed line {line!r}"


# ---------------------------------------------------------- metrics server
class TestDebugEndpoints:
    def test_debug_decisions_and_trace(self, recorder):
        with spans.start_trace("decision", pod="ns/p") as t:
            with spans.span("decide"):
                pass
            t.meta["source"] = "llm"
        server = MetricsServer(
            lambda: {"x": 1}, port=0, host="127.0.0.1",
            flight_recorder=recorder,
        )
        server.start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            listing = json.loads(
                urllib.request.urlopen(f"{base}/debug/decisions").read()
            )
            assert listing["recorder"]["held"] == 1
            assert listing["traces"][0]["trace_id"] == t.trace_id
            assert listing["traces"][0]["meta"]["source"] == "llm"
            full = json.loads(urllib.request.urlopen(
                f"{base}/debug/trace/{t.trace_id}"
            ).read())
            assert {s["name"] for s in full["spans"]} == {
                "decision", "decide",
            }
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(f"{base}/debug/trace/nope")
            assert err.value.code == 404
            export = urllib.request.urlopen(
                f"{base}/debug/export"
            ).read().decode()
            assert json.loads(export.splitlines()[0])["trace_id"] == t.trace_id
            # since= cursor returns nothing once consumed
            empty = json.loads(urllib.request.urlopen(
                f"{base}/debug/decisions?since={listing['traces'][0]['seq']}"
            ).read())
            assert empty["traces"] == []
        finally:
            server.stop()

    def test_debug_decisions_n_cut_surfaces_as_truncated(self, recorder):
        """The documented resume contract: a cursor walk (since= present)
        must reach EVERY held trace even when each page's n cut engages —
        the cut is oldest-first with truncated=true, never a silent
        newest-n skip. Without a cursor the endpoint keeps its
        recent-traces view (newest n)."""
        for i in range(12):
            with spans.start_trace("decision", pod=f"ns/p{i}"):
                pass
        server = MetricsServer(
            lambda: {}, port=0, host="127.0.0.1",
            flight_recorder=recorder,
        )
        server.start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            walked, cursor, pages = [], 0, 0
            while True:
                page = json.loads(urllib.request.urlopen(
                    f"{base}/debug/decisions?n=5&since={cursor}"
                ).read())
                walked.extend(t["seq"] for t in page["traces"])
                pages += 1
                if not page["truncated"]:
                    break
                assert page["next_cursor"] > cursor
                cursor = page["next_cursor"]
            assert walked == list(range(1, 13))
            assert pages == 3
            # no cursor: newest n, oldest-first within the window
            recent = json.loads(urllib.request.urlopen(
                f"{base}/debug/decisions?n=5"
            ).read())
            assert [t["seq"] for t in recent["traces"]] == [8, 9, 10, 11, 12]
        finally:
            server.stop()

    def test_debug_engine_endpoint(self, recorder):
        class FakeEngine:
            max_slots = 8
            free_slots = 6

            class kv:
                num_pages = 100
                pages_free = 75

            stats = {"decode_tokens": 500, "prefix_hits": 3,
                     "prefix_prefills": 1}

        sampler = EngineSampler(FakeEngine(), interval_s=0.05, window=16)
        sampler.sample_once()
        server = MetricsServer(
            lambda: {"engine_telemetry": sampler.latest()},
            port=0, host="127.0.0.1",
            flight_recorder=recorder, engine_sampler=sampler,
        )
        server.start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            series = json.loads(
                urllib.request.urlopen(f"{base}/debug/engine").read()
            )
            assert series["series"]["batch_occupancy"][-1][1] == 0.25
            assert series["series"]["kv_page_util"][-1][1] == 0.25
            metrics_text = urllib.request.urlopen(
                f"{base}/metrics"
            ).read().decode()
            assert (
                "llm_scheduler_engine_telemetry_batch_occupancy 0.25"
                in metrics_text
            )
        finally:
            server.stop()

    def test_engine_endpoint_404_without_sampler(self, recorder):
        server = MetricsServer(
            lambda: {}, port=0, host="127.0.0.1", flight_recorder=recorder,
        )
        server.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/debug/engine"
                )
            assert err.value.code == 404
        finally:
            server.stop()

    def test_handler_survives_client_disconnect(self, recorder):
        """A client that closes mid-exchange must not wedge or kill the
        server: the next request still answers (the handler class also
        carries a socket timeout so stalled scrapers can't pin threads)."""
        server = MetricsServer(
            lambda: {"x": list(range(5000))}, port=0, host="127.0.0.1",
            flight_recorder=recorder,
        )
        assert server._server.RequestHandlerClass.timeout == 10.0
        server.start()
        try:
            for _ in range(3):
                sock = socket.create_connection(
                    ("127.0.0.1", server.port), timeout=2
                )
                sock.sendall(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
                sock.close()  # vanish before reading the response
            # server still alive and serving
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/healthz", timeout=5
            ).read()
            assert body == b"ok"
        finally:
            server.stop()


# ---------------------------------------------------------------- sampler
class TestEngineSampler:
    class FakeEngine:
        def __init__(self):
            self.max_slots = 4
            self.free_slots = 4

            class KV:
                num_pages = 64
                pages_free = 64

            self.kv = KV()
            self.stats = {"decode_tokens": 0, "prefix_hits": 0,
                          "prefix_prefills": 0}

    def test_rates_and_series(self):
        eng = self.FakeEngine()
        clock = {"t": 100.0}
        sampler = EngineSampler(
            eng, interval_s=1.0, window=4, clock=lambda: clock["t"]
        )
        sampler.sample_once()
        eng.stats["decode_tokens"] = 500
        eng.free_slots = 1
        eng.kv.pages_free = 16
        eng.stats["prefix_hits"] = 9
        eng.stats["prefix_prefills"] = 1
        clock["t"] = 102.0
        out = sampler.sample_once()
        assert out["tokens_per_s"] == pytest.approx(250.0)
        assert out["batch_occupancy"] == pytest.approx(0.75)
        assert out["kv_page_util"] == pytest.approx(0.75)
        assert out["prefix_cache_hit_rate"] == pytest.approx(0.9)
        latest = sampler.latest()
        assert latest["tokens_per_s"] == pytest.approx(250.0)
        assert latest["samples_taken"] == 2
        # ring bounded at window
        for _ in range(10):
            clock["t"] += 1.0
            sampler.sample_once()
        series = sampler.series()
        assert len(series["series"]["tokens_per_s"]) == 4
        # ages are relative to the newest sample (newest == 0)
        assert series["series"]["tokens_per_s"][-1][0] == 0.0

    def test_background_thread(self):
        eng = self.FakeEngine()
        sampler = EngineSampler(eng, interval_s=0.05, window=32)
        sampler.start()
        try:
            deadline = time.time() + 5
            while sampler.samples_taken < 3 and time.time() < deadline:
                time.sleep(0.02)
            assert sampler.samples_taken >= 3
        finally:
            sampler.stop()


# --------------------------------------------------- scheduler integration
def make_stack(cluster, backend):
    client = DecisionClient(
        backend=backend, cache=DecisionCache(), retry_delay=0.0,
    )
    return Scheduler(
        cluster, cluster, client,
        scheduler_name=SCHEDULER_NAME, snapshot_ttl_s=300.0,
        prefix_prewarm_s=0.0,
    )


class TestSchedulerTraces:
    def test_decision_trace_through_fake_cluster(self, recorder):
        """A scheduled pod leaves a retrievable flight-recorder trace whose
        span tree includes snapshot, decide (with a backend child), and
        bind — and whose wall times are consistent with the recorded phase
        histograms."""
        async def run():
            cluster = synthetic_cluster(3)
            scheduler = make_stack(cluster, StubBackend())
            task = asyncio.create_task(scheduler.run())
            for pod in fixture_pods():
                cluster.add_pod(pod)
            async with async_deadline(20):
                while cluster.bind_count < 3:
                    await asyncio.sleep(0.01)
            scheduler.stop()
            cluster.close()
            async with async_deadline(10):
                await task
            return scheduler

        scheduler = asyncio.run(run())
        traces = recorder.list(n=50)
        bound = [t for t in traces if t["meta"].get("outcome") == "bound"]
        assert len(bound) == 3
        full = recorder.get(bound[0]["trace_id"])
        names = {s["name"] for s in full["spans"]}
        assert {"decision", "snapshot", "decide", "bind"} <= names
        # the decide span parents the backend span
        decide = next(s for s in full["spans"] if s["name"] == "decide")
        backend_sp = next(s for s in full["spans"] if s["name"] == "backend")
        assert backend_sp["parent_id"] == decide["span_id"]
        assert full["meta"]["source"] in ("llm", "cache")
        assert "cache_key" in full["meta"]
        assert full["meta"]["cache_generation"] == 0

        # wall-time consistency vs the phase histograms: summed span time
        # per phase matches the PhaseRecorder totals within tolerance
        # (same perf_counter intervals measured two ways)
        phases = scheduler.phases.snapshot()
        for phase in ("snapshot", "decide", "bind"):
            span_total = sum(
                s["dur_ms"]
                for t in traces
                for s in recorder.get(t["trace_id"])["spans"]
                if s["name"] == phase and s["dur_ms"] is not None
            )
            recorded = phases[phase]["total_ms"]
            assert span_total == pytest.approx(recorded, rel=0.35, abs=2.0), (
                phase, span_total, recorded,
            )

    def test_fallback_reason_lands_in_meta(self, recorder):
        async def run():
            cluster = synthetic_cluster(2)
            backend = StubBackend()
            backend.fail_next = 10**6  # every call fails -> fallback
            client = DecisionClient(
                backend, cache=DecisionCache(), max_retries=2,
                retry_delay=0.0,
            )
            scheduler = Scheduler(
                cluster, cluster, client,
                scheduler_name=SCHEDULER_NAME, snapshot_ttl_s=300.0,
                prefix_prewarm_s=0.0,
            )
            task = asyncio.create_task(scheduler.run())
            cluster.add_pod(fixture_pods()[0])
            async with async_deadline(20):
                while cluster.bind_count < 1:
                    await asyncio.sleep(0.01)
            scheduler.stop()
            cluster.close()
            async with async_deadline(10):
                await task

        asyncio.run(run())
        entries = [
            e for e in recorder.list(n=50)
            if e["meta"].get("source") == "fallback"
        ]
        assert entries
        assert entries[0]["meta"]["fallback_reason"].startswith(
            "retries_exhausted"
        )


# ----------------------------------------------------- replica propagation
class TestReplicaSpanPropagation:
    def test_trace_id_survives_wire_roundtrip(self, recorder):
        """The trace id crosses the replica RPC and the stitched trace
        contains BOTH client-side and replica-side spans."""
        from k8s_llm_scheduler_tpu.sched.replica import (
            ReplicaClient,
            ReplicaServer,
        )
        from k8s_llm_scheduler_tpu.testing import synthetic_cluster as _sc

        cluster = _sc(3)
        nodes = cluster.get_node_metrics()
        pod_raw = fixture_pods()[0]
        from k8s_llm_scheduler_tpu.cluster.interface import raw_pod_to_spec

        pod = raw_pod_to_spec(pod_raw)
        server = ReplicaServer(StubBackend(), host="127.0.0.1", port=0)
        client = ReplicaClient("127.0.0.1", server.port,
                               request_timeout_s=20.0)
        try:
            with spans.start_trace("decision", pod=pod.name) as trace:
                with spans.span("decide"):
                    decision = client.get_scheduling_decision(pod, nodes)
            assert decision.selected_node
            names = [s.name for s in trace.spans]
            assert "replica.decide" in names
            remote = next(
                s for s in trace.spans if s.name == "replica.decide"
            )
            # the remote root carries OUR trace id and parents under the
            # client-side span that made the call
            assert remote.trace_id == trace.trace_id
            client_side = {
                s.span_id for s in trace.spans
                if s.name in ("decision", "decide")
            }
            assert remote.parent_id in client_side
            assert remote.dur_ms is not None
            # tree stitches: the remote span nests under decide
            tree = trace.span_tree()
            decide_node = next(
                c for c in tree["children"] if c["name"] == "decide"
            )
            assert [
                c["name"] for c in decide_node["children"]
            ] == ["replica.decide"]
        finally:
            client.close()
            server.close()
            cluster.close()

    def test_untraced_requests_skip_the_machinery(self, recorder):
        from k8s_llm_scheduler_tpu.cluster.interface import raw_pod_to_spec
        from k8s_llm_scheduler_tpu.sched.replica import (
            ReplicaClient,
            ReplicaServer,
        )

        cluster = synthetic_cluster(2)
        nodes = cluster.get_node_metrics()
        pod = raw_pod_to_spec(fixture_pods()[0])
        server = ReplicaServer(StubBackend(), host="127.0.0.1", port=0)
        client = ReplicaClient("127.0.0.1", server.port,
                               request_timeout_s=20.0)
        try:
            decision = client.get_scheduling_decision(pod, nodes)
            assert decision.selected_node
            assert recorder.list() == []  # no ambient trace, no records
        finally:
            client.close()
            server.close()
            cluster.close()


# ----------------------------------------------------- engine span shapes
class TestEngineSpanAttachment:
    def test_attach_item_spans_apportions_by_tokens(self, recorder):
        """The worker-side attacher (fast-tier double of the real wave
        path): admission wait from the queue interval, prefill/decode
        splitting the wave wall time by token counts."""
        from k8s_llm_scheduler_tpu.engine.local import (
            LocalLLMBackend,
            _WorkItem,
        )

        class Handle:
            pass

        class Fin:
            token_ids = list(range(30))

        with spans.start_trace("decision") as trace:
            item = _WorkItem([1, 2], list(range(70)), ("g",))
            item.trace = spans.capture()
        handle = Handle()
        handle.submitted_at = item.enqueued_at + 0.010
        now = handle.submitted_at + 0.100
        LocalLLMBackend._attach_item_spans(item, handle, Fin(), now)
        by_name = {s.name: s for s in trace.spans}
        assert by_name["admission_wait"].dur_ms == pytest.approx(10.0)
        assert by_name["prefill"].attrs["tokens"] == 70
        assert by_name["decode"].attrs["tokens"] == 30
        assert by_name["prefill"].dur_ms == pytest.approx(70.0)
        assert by_name["decode"].dur_ms == pytest.approx(30.0)
        # the split reconstructs the wave wall time exactly
        assert (
            by_name["prefill"].dur_ms + by_name["decode"].dur_ms
        ) == pytest.approx(100.0)

    def test_attach_without_trace_is_noop(self, recorder):
        from k8s_llm_scheduler_tpu.engine.local import (
            LocalLLMBackend,
            _WorkItem,
        )

        item = _WorkItem([1], [1, 2], ("g",))
        assert item.trace is None

        class Fin:
            token_ids = [1]

        class Handle:
            submitted_at = item.enqueued_at

        # must not raise
        LocalLLMBackend._attach_item_spans(
            item, Handle(), Fin(), time.perf_counter()
        )


# ------------------------------------------------- real engine (slow tier)
@pytest.mark.slow
class TestRealEngineTrace:
    """The acceptance-criterion path: a decision through the REAL tiny
    engine produces a trace whose decide span carries prefill and decode
    children with genuine token counts, consistent with the phase
    histograms. jit-compiles a model — full suite only (TESTING.md)."""

    def test_wave_decision_trace(self, recorder):
        import jax.numpy as jnp

        from k8s_llm_scheduler_tpu.engine.local import build_local_backend
        from k8s_llm_scheduler_tpu.models.configs import LlamaConfig

        cfg = LlamaConfig(
            name="obs-test", vocab_size=512, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=4096,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        backend = build_local_backend(
            cfg=cfg, max_slots=4, num_pages=256, page_size=64,
            prefill_buckets=(512, 1024, 2048, 4096),
            chunk_steps=16, temperature=0.0, max_new_tokens=160,
        )
        try:
            async def run():
                cluster = synthetic_cluster(3)
                scheduler = make_stack(cluster, backend)
                task = asyncio.create_task(scheduler.run())
                for pod in fixture_pods():
                    cluster.add_pod(pod)
                async with async_deadline(300):
                    while cluster.bind_count < 3:
                        await asyncio.sleep(0.02)
                scheduler.stop()
                cluster.close()
                async with async_deadline(30):
                    await task
                return scheduler

            scheduler = asyncio.run(run())
        finally:
            backend.close()

        llm_traces = [
            recorder.get(e["trace_id"])
            for e in recorder.list(n=50)
            if e["meta"].get("source") == "llm"
        ]
        assert llm_traces, "no LLM-sourced decision trace recorded"
        full = llm_traces[0]
        by_name = {s["name"]: s for s in full["spans"]}
        assert {"decision", "snapshot", "decide", "backend",
                "admission_wait", "prefill", "decode", "bind"} <= set(by_name)
        # token counts are genuine: prefill carries the pod suffix length,
        # decode the emitted decision length
        assert by_name["prefill"]["attrs"]["tokens"] > 0
        assert by_name["decode"]["attrs"]["tokens"] > 0
        # engine-side spans hang under the client's backend span
        assert by_name["prefill"]["parent_id"] == by_name["backend"]["span_id"]
        assert by_name["decode"]["parent_id"] == by_name["backend"]["span_id"]
        # wall-time consistency: the engine-side split reconstructs the
        # wave interval, which fits inside the decide span; decide fits
        # inside the recorded decide-phase histogram's max
        wave_ms = (
            by_name["prefill"]["dur_ms"] + by_name["decode"]["dur_ms"]
        )
        assert wave_ms <= by_name["decide"]["dur_ms"] * 1.05
        phases = scheduler.phases.snapshot()
        assert by_name["decide"]["dur_ms"] <= phases["decide"]["max_ms"] * 1.05
        assert phases["decide"]["p99_ms"] >= phases["decide"]["p50_ms"]

    def test_paged_generate_trace(self, recorder):
        """The PAGED path's ambient engine spans (prefill_dispatch,
        per-chunk decode_chunk) land in a trace opened around generate()
        — generate runs on the caller's thread, which is what makes the
        `cli complete` trace wiring work."""
        import jax.numpy as jnp

        from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
        from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
        from k8s_llm_scheduler_tpu.models.llama import init_params
        import jax

        cfg = LlamaConfig(
            name="obs-paged", vocab_size=512, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=2048,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        engine = InferenceEngine(
            init_params(jax.random.PRNGKey(0), cfg), cfg,
            num_pages=64, page_size=64, max_slots=2, max_pages_per_seq=8,
            prefill_buckets=(128, 256), chunk_steps=8, temperature=0.0,
        )
        with spans.start_trace("completion") as trace:
            fin = engine.generate(list(range(1, 40)), max_new_tokens=24)
        assert fin.token_ids
        by_name = {}
        for s in trace.spans:
            by_name.setdefault(s.name, []).append(s)
        assert "prefill_dispatch" in by_name
        assert by_name["prefill_dispatch"][0].attrs["tokens"] == 39
        chunks = by_name.get("decode_chunk", [])
        assert chunks, "no decode_chunk spans from the paged step loop"
        # emitted token counts across chunks cover the generation
        assert sum(
            c.attrs.get("tokens", 0) for c in chunks
        ) >= len(fin.token_ids) - 1
