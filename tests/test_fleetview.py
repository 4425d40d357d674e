"""Fleet telemetry aggregation (observability/fleetview.py) + the
`telemetry_pull` replica-wire op + paginated debug surfaces.

The acceptance-bar scenario lives in TestFleetE2E: a 4-replica fleet's
histograms/traces/flight-recorder slices merge into one aggregated view,
and the fleet p99 computed from MERGED buckets equals recomputation from
the raw samples within one bucket width (here: exactly the same bucket).
Edge cases: replica joining mid-scrape, replica death mid-pull
(degrade + staleness), merged-bucket boundary identity with the
single-process exposition.
"""

import asyncio
import json
import sys
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import pytest

from k8s_llm_scheduler_tpu.engine.backend import StubBackend
from k8s_llm_scheduler_tpu.fleet import Fleet
from k8s_llm_scheduler_tpu.observability import fleetview, spans
from k8s_llm_scheduler_tpu.observability.fleetview import (
    FleetAggregator,
    build_telemetry,
    render_top,
)
from k8s_llm_scheduler_tpu.observability.metrics import (
    MetricsServer,
    render_prometheus,
)
from k8s_llm_scheduler_tpu.observability.spans import FlightRecorder
from k8s_llm_scheduler_tpu.observability.trace import (
    HIST_KEY,
    PhaseRecorder,
    hist_percentiles,
)
from k8s_llm_scheduler_tpu.testing import pod_burst, synthetic_cluster

SCHEDULER_NAME = "ai-llama-scheduler"


def _recorder_with(durations_s):
    rec = PhaseRecorder()
    for d in durations_s:
        rec.record("decide", d)
    return rec


def _make_trace(recorder, name="decision", trace_id=None, parent_id=None,
                **meta):
    with spans.start_trace(
        name, recorder=recorder, trace_id=trace_id, parent_id=parent_id,
    ) as t:
        with spans.span("decide"):
            pass
        if meta:
            t.set_meta(**meta)
    return t


class TestHistogramMerge:
    def test_merged_percentiles_match_combined_raw_buckets(self):
        """Merging N replicas' buckets and recomputing percentiles is
        IDENTICAL to bucketing the union of raw samples — the shared
        fixed ladder makes the merge lossless relative to bucketing."""
        import random

        rng = random.Random(7)
        per_replica = [
            [rng.uniform(0.001, 0.4) for _ in range(200)] for _ in range(4)
        ]
        agg = FleetAggregator()
        for i, samples in enumerate(per_replica):
            rec = _recorder_with(samples)
            agg.add_local(f"r{i}", lambda rec=rec: {"phases": rec.snapshot()})
        agg.pull_all()
        merged = agg.merged_stats()["phases"]["decide"]

        union = _recorder_with(
            [s for samples in per_replica for s in samples]
        )
        expected = union.snapshot()["decide"]
        assert merged[HIST_KEY]["counts"] == expected[HIST_KEY]["counts"]
        for key in ("count", "p50_ms", "p95_ms", "p99_ms", "max_ms"):
            assert merged[key] == pytest.approx(expected[key])

    def test_merged_counters_sum_and_strings_survive(self):
        agg = FleetAggregator()
        agg.add_local("a", lambda: {
            "total_scheduled": 3, "client": {"invalid_decisions": 1},
            "state": "ok", "per_wave": [1, 2],
        })
        agg.add_local("b", lambda: {
            "total_scheduled": 4, "client": {"invalid_decisions": 0},
            "state": "ok",
        })
        agg.pull_all()
        merged = agg.merged_stats()
        assert merged["total_scheduled"] == 7
        assert merged["client"]["invalid_decisions"] == 1
        assert merged["state"] == "ok"
        assert "per_wave" not in merged  # lists stay per-replica

    def test_single_source_exposition_identical_to_local(self):
        """Merged-histogram bucket-boundary identity with the
        single-process exposition: one source in, the merged exposition
        is byte-identical for the shared families."""
        rec = _recorder_with([0.002, 0.05, 0.3])
        stats = {"total_scheduled": 3, "phases": rec.snapshot()}
        agg = FleetAggregator()
        agg.add_local("only", lambda: stats)
        agg.pull_all()
        assert agg.render_prometheus() == render_prometheus(stats)


class TestAggregatorMembership:
    def test_replica_joins_mid_scrape(self):
        """A replica joining between rounds contributes its partial bucket
        history on the next round — cumulative histograms make the late
        join sound with no special casing."""
        rec_a = _recorder_with([0.01] * 50)
        agg = FleetAggregator()
        agg.add_local("a", lambda: {"phases": rec_a.snapshot()})
        agg.pull_all()
        assert agg.merged_stats()["phases"]["decide"]["count"] == 50
        rec_b = _recorder_with([0.01] * 20)  # younger member, less history
        agg.add_local("b", lambda: {"phases": rec_b.snapshot()})
        agg.pull_all()
        assert agg.merged_stats()["phases"]["decide"]["count"] == 70
        status = agg.source_status()
        assert not status["a"]["stale"] and not status["b"]["stale"]

    def test_replica_death_degrades_to_survivors_and_marks_stale(self):
        clock = {"t": 100.0}
        agg = FleetAggregator(stale_after_s=5.0, clock=lambda: clock["t"])
        rec_a = _recorder_with([0.01] * 10)
        state = {"alive": True}

        def dying_pull(since):
            if not state["alive"]:
                raise ConnectionError("replica gone")
            return build_telemetry({"phases": rec_a.snapshot(),
                                    "total_scheduled": 10})

        agg.add_source("dying", dying_pull)
        agg.add_local("survivor", lambda: {"total_scheduled": 5})
        assert agg.pull_all() == {"ok": 2, "failed": 0, "sources": 2}
        state["alive"] = False
        clock["t"] += 2.0
        round2 = agg.pull_all()
        assert round2 == {"ok": 1, "failed": 1, "sources": 2}
        # within the staleness grace: last-known payload still serves
        assert not agg.source_status()["dying"]["stale"]
        assert agg.merged_stats()["total_scheduled"] == 15
        clock["t"] += 10.0
        agg.pull_all()
        status = agg.source_status()
        assert status["dying"]["stale"] and status["dying"]["failures"] >= 2
        assert not status["survivor"]["stale"]
        # degraded, not blanked: the dead member's history is retained
        # and marked, the survivor keeps reporting
        assert agg.merged_stats()["total_scheduled"] == 15
        assert "STALE" in render_top(agg)


class TestTraceStitching:
    def test_cross_replica_traces_fuse_by_trace_id(self):
        """A coordinator-side decision trace and the worker-side
        replica.decide trace (same trace id riding the decision frame)
        merge into ONE entry with the union of spans."""
        rec_coord, rec_worker = FlightRecorder(16), FlightRecorder(16)
        coord = _make_trace(rec_coord, source="llm")
        # the worker opens a remote-rooted trace UNDER the coordinator's
        # trace id (sched/replica.py ReplicaServer does exactly this)
        _make_trace(
            rec_worker, name="replica.decide",
            trace_id=coord.trace_id, parent_id=coord.root.span_id,
        )
        agg = FleetAggregator()
        agg.add_local("coord", lambda: {}, recorder=rec_coord)
        agg.add_local("worker", lambda: {}, recorder=rec_worker)
        agg.pull_all()
        traces = agg.traces()
        assert len(traces) == 1
        [entry] = traces
        assert entry["trace_id"] == coord.trace_id
        assert sorted(entry["sources"]) == ["coord", "worker"]
        names = {s["name"] for s in entry["spans"]}
        assert {"decision", "replica.decide", "decide"} <= names
        # the coordinator's (earlier) root fields win
        assert entry["name"] == "decision"
        assert entry["meta"]["source"] == "llm"

    def test_cursor_advances_across_rounds(self):
        rec = FlightRecorder(16)
        agg = FleetAggregator()
        agg.add_local("r", lambda: {}, recorder=rec)
        _make_trace(rec)
        agg.pull_all()
        assert len(agg.traces()) == 1
        agg.pull_all()  # nothing new: cursor prevents re-shipping
        assert len(agg.traces()) == 1
        _make_trace(rec)
        agg.pull_all()
        assert len(agg.traces()) == 2


class TestPagination:
    def test_export_slices_resume_path(self):
        rec = FlightRecorder(64)
        ids = [_make_trace(rec).trace_id for _ in range(10)]
        one = len(json.dumps(rec.export_slices()[0][0],
                             separators=(",", ":")))
        collected = []
        cursor = 0
        rounds = 0
        while True:
            entries, cursor, truncated = rec.export_slices(
                since_seq=cursor, max_bytes=3 * one + 10,
            )
            collected.extend(entries)
            rounds += 1
            if not truncated:
                break
            assert rounds < 20
        assert [e["trace_id"] for e in collected] == ids
        # an oversized single trace still ships (cursor can't wedge)
        entries, _, _ = rec.export_slices(max_bytes=1)
        assert len(entries) == 1

    def test_debug_decisions_and_export_pagination(self):
        rec = FlightRecorder(64)
        for _ in range(8):
            _make_trace(rec)
        server = MetricsServer(
            lambda: {}, port=0, host="127.0.0.1", flight_recorder=rec,
        )
        server.start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            body = json.loads(urllib.request.urlopen(
                f"{base}/debug/decisions?max_bytes=600"
            ).read())
            assert body["truncated"] is True
            assert 0 < len(body["traces"]) < 8
            assert body["next_cursor"] == body["traces"][-1]["seq"]
            # uncapped: everything, not truncated
            body = json.loads(urllib.request.urlopen(
                f"{base}/debug/decisions"
            ).read())
            assert body["truncated"] is False and len(body["traces"]) == 8

            # export: resume via the trailer's next_cursor
            seen = []
            cursor = 0
            for _ in range(20):
                lines = urllib.request.urlopen(
                    f"{base}/debug/export?since={cursor}&max_bytes=2000"
                ).read().decode().splitlines()
                trailer = json.loads(lines[-1])
                if trailer.get("truncated"):
                    seen.extend(json.loads(x) for x in lines[:-1])
                    cursor = trailer["next_cursor"]
                    continue
                seen.extend(json.loads(x) for x in lines)
                break
            assert len(seen) == 8
            assert len({e["trace_id"] for e in seen}) == 8
        finally:
            server.stop()


class TestWireTelemetryPull:
    def test_round_trip_with_cursor_and_caps(self):
        from k8s_llm_scheduler_tpu.sched.replica import (
            ReplicaClient,
            ReplicaServer,
        )

        rec = FlightRecorder(32)
        recorder_stats = _recorder_with([0.01, 0.02, 0.4])
        for _ in range(6):
            _make_trace(rec)

        def telemetry_fn(req):
            return build_telemetry(
                {"phases": recorder_stats.snapshot(), "total_scheduled": 3},
                rec,
                since_seq=int(req.get("since", 0)),
                max_traces=int(req.get("max_traces", 256)),
                max_bytes=int(req.get("max_bytes", 1 << 20)),
            )

        server = ReplicaServer(
            StubBackend(), port=0, telemetry_fn=telemetry_fn,
        )
        client = ReplicaClient("localhost", server.port)
        try:
            payload = client.telemetry_pull(max_traces=4)
            assert payload["truncated"] is True
            assert len(payload["traces"]) == 4
            assert payload["stats"]["total_scheduled"] == 3
            # histograms rode the wire as bucket dicts
            hist = payload["stats"]["phases"]["decide"][HIST_KEY]
            assert hist["count"] == 3
            rest = client.telemetry_pull(
                since_seq=payload["next_cursor"], max_traces=4,
            )
            assert rest["truncated"] is False
            assert len(rest["traces"]) == 2
            got = {e["trace_id"] for e in payload["traces"]}
            got |= {e["trace_id"] for e in rest["traces"]}
            assert len(got) == 6
        finally:
            client.close()
            server.close()

    def test_default_telemetry_serves_backend_stats(self):
        from k8s_llm_scheduler_tpu.sched.replica import (
            ReplicaClient,
            ReplicaServer,
        )

        server = ReplicaServer(StubBackend(), port=0)
        client = ReplicaClient("localhost", server.port)
        try:
            payload = client.telemetry_pull()
            assert "stats" in payload and "traces" in payload
        finally:
            client.close()
            server.close()

    def test_aggregator_over_the_wire(self):
        from k8s_llm_scheduler_tpu.sched.replica import (
            ReplicaClient,
            ReplicaServer,
        )

        recs = [_recorder_with([0.01 * (i + 1)] * 20) for i in range(2)]
        servers = [
            ReplicaServer(
                StubBackend(), port=0,
                telemetry_fn=lambda req, r=recs[i]: build_telemetry(
                    {"phases": r.snapshot(), "total_scheduled": 20},
                ),
            )
            for i in range(2)
        ]
        clients = [
            ReplicaClient("localhost", s.port) for s in servers
        ]
        agg = FleetAggregator()
        for i, c in enumerate(clients):
            agg.add_replica_client(f"w{i}", c)
        try:
            assert agg.pull_all()["ok"] == 2
            merged = agg.merged_stats()
            assert merged["total_scheduled"] == 40
            assert merged["phases"]["decide"]["count"] == 40
        finally:
            for c in clients:
                c.close()
            for s in servers:
                s.close()


class TestFleetE2E:
    async def test_four_replica_merged_view(self):
        """ACCEPTANCE: a 4-replica fleet's histograms, traces, and
        flight-recorder slices merge into one aggregated view; fleet p99
        from merged buckets equals recomputation from raw samples within
        one bucket width (same ladder -> same bucket, asserted exactly)."""
        cluster = synthetic_cluster(8)
        fleet = Fleet(
            cluster, cluster,
            lambda i: StubBackend(latency_s=0.005),
            n_replicas=4, lease_ttl_s=60.0,
            list_pending=lambda: cluster.pending_pods(SCHEDULER_NAME),
        )
        # tee every replica's raw decide durations for the recomputation
        raw_decides: list[float] = []
        for replica in fleet.replicas:
            orig = replica.scheduler.phases.record

            def tee(name, seconds, _orig=orig):
                if name == "decide":
                    raw_decides.append(seconds)
                _orig(name, seconds)

            replica.scheduler.phases.record = tee

        for raw in pod_burst(120, scheduler_name=SCHEDULER_NAME,
                             distinct_shapes=12):
            cluster.add_pod(raw)
        await fleet.start(lease_threads=False)
        try:
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if fleet.get_stats()["total_scheduled"] >= 120:
                    break
                await asyncio.sleep(0.01)
            agg = fleet.aggregator()
            agg.pull_all()
            merged = agg.merged_stats()
            pct = agg.fleet_percentiles("decide")
        finally:
            await fleet.stop()

        # every replica contributed to the merged counters
        assert merged["total_scheduled"] == 120
        assert pct is not None and pct["count"] == len(raw_decides) >= 120
        # fleet p99 from merged buckets == recomputation from the raw
        # samples, within one bucket width: re-bucket the raw union and
        # the percentile must land in the SAME bucket (identical value —
        # both estimators report the bucket's upper bound)
        union = _recorder_with(raw_decides)
        # rename: _recorder_with records under "decide" already
        expected = hist_percentiles(
            union.snapshot()["decide"][HIST_KEY]["counts"]
        )
        assert pct["p99_ms"] == pytest.approx(expected[2])
        assert pct["p50_ms"] == pytest.approx(expected[0])
        # raw nearest-rank p99 sits inside the merged p99's bucket
        ordered = sorted(raw_decides)
        raw_p99_ms = ordered[
            min(len(ordered) - 1, int(0.99 * len(ordered)))
        ] * 1000.0
        assert raw_p99_ms <= pct["p99_ms"] <= max(
            raw_p99_ms * 2.0, 0.2
        )
        # traces merged from the shared ring; decision traces present
        traces = agg.traces(n=500)
        assert any(e.get("name") == "decision" for e in traces)
        # per-replica breakdown renders
        frame = render_top(agg)
        assert "fleet decide" in frame and "replica-0" in frame
