"""SLO burn-rate engine (observability/slo.py).

Covers the objective grammar, the conservative bucket-quantized violation
counting, multi-window (fast+slow) trip semantics with an injected clock,
rising-edge hooks, the breaker advisory, the /debug/slo surface, and the
end-to-end acceptance path: synthetic latency regression -> /debug/slo
trip -> canary burn-in rollback fires.
"""

import json
import sys
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import pytest

from k8s_llm_scheduler_tpu.core.breaker import CircuitBreaker
from k8s_llm_scheduler_tpu.observability.slo import (
    SloEngine,
    SloObjective,
    _violations_above,
    from_config,
)
from k8s_llm_scheduler_tpu.observability.trace import (
    BUCKET_BOUNDS_S,
    PhaseRecorder,
)


class TestObjectiveGrammar:
    def test_from_dict_roundtrip(self):
        obj = SloObjective.from_dict({
            "name": "decide_latency", "kind": "latency",
            "phase": "decide", "threshold_ms": 250.0, "budget": 0.01,
        })
        assert obj.fast_threshold == 14.4 and obj.slow_threshold == 6.0

    def test_throughput_thresholds_default_to_one(self):
        obj = SloObjective(name="f", kind="throughput", min_per_s=5.0)
        assert obj.fast_threshold == 1.0 and obj.slow_threshold == 1.0

    def test_unknown_kind_and_keys_rejected(self):
        with pytest.raises(ValueError, match="kind"):
            SloObjective(name="x", kind="weird")
        with pytest.raises(ValueError, match="unknown keys"):
            SloObjective.from_dict(
                {"name": "x", "kind": "latency", "thresh": 1}
            )
        with pytest.raises(ValueError, match="budget"):
            SloObjective(name="x", kind="latency", budget=0.0)

    def test_from_config_disabled_or_empty_is_none(self):
        assert from_config({}, lambda: {}) is None
        assert from_config({"enabled": False}, lambda: {}) is None
        assert from_config(
            {"enabled": True, "objectives": []}, lambda: {}
        ) is None
        eng = from_config(
            {
                "enabled": True,
                "fast_window_s": 10,
                "objectives": [{"name": "a", "kind": "latency"}],
            },
            lambda: {},
        )
        assert eng is not None and eng.fast_window_s == 10.0


class TestViolationCounting:
    def test_conservative_bucket_lower_bound(self):
        """Only events whose bucket LOWER bound >= threshold count — the
        bucket containing the threshold never does (no false trips from
        quantization)."""
        rec = PhaseRecorder()
        rec.record("p", 0.001)    # well below
        rec.record("p", 0.018)    # in the (12.8, 25.6]ms bucket
        rec.record("p", 0.060)    # lower bound 51.2ms >= 20ms: violation
        rec.record("p", 5.0)      # far above: violation
        counts = rec.snapshot()["p"]["_hist"]["counts"]
        assert _violations_above(counts, threshold_ms=20.0) == 2
        # overflow bucket counts when threshold is below its lower bound
        rec2 = PhaseRecorder()
        rec2.record("p", BUCKET_BOUNDS_S[-1] * 3)
        counts2 = rec2.snapshot()["p"]["_hist"]["counts"]
        assert _violations_above(counts2, BUCKET_BOUNDS_S[-1] * 1000) == 1


def _latency_engine(clock, **kw):
    rec = PhaseRecorder()
    state = {"scheduled": 0}

    def provider():
        return {
            "phases": rec.snapshot(),
            "total_scheduled": state["scheduled"],
            "failed_bindings": state.get("failed", 0),
        }

    eng = SloEngine(
        [SloObjective(
            name="decide", kind="latency", phase="decide",
            threshold_ms=10.0, budget=0.01, **kw,
        )],
        provider,
        fast_window_s=10.0,
        slow_window_s=100.0,
        clock=lambda: clock["t"],
    )
    return eng, rec, state


class TestSnapshotThinning:
    def test_dense_evaluate_cadence_keeps_ring_bounded(self):
        """A sub-interval evaluate cadence must not accumulate one full
        stats tree per tick: aged snapshots thin to POINTS_PER_WINDOW
        resolution per window tier, so memory is bounded by the window
        geometry, not interval_s."""
        clock = {"t": 0.0}
        eng, rec, _ = _latency_engine(clock)  # fast 10s / slow 100s
        rec.record("decide", 0.001)
        # 10k ticks at 0.05s — two full slow windows of dense sampling
        for _ in range(10_000):
            clock["t"] += 0.05
            eng.evaluate()
        held = eng.snapshot()["snapshots_held"]
        # <= ~POINTS_PER_WINDOW per tier (+ slack for the boundary keeps)
        assert held <= 2 * eng.POINTS_PER_WINDOW + 4, held
        # burns still evaluate with full-window coverage after thinning
        detail = eng.evaluate()["decide"]
        assert detail["slow"]["window_covered_s"] >= 99.0
        assert detail["fast"]["window_covered_s"] >= 9.0


class TestMultiWindow:
    def test_fast_burn_alone_does_not_trip(self):
        """A long healthy history keeps the slow window below threshold:
        the fast+slow pairing is exactly what stops a blip from paging."""
        clock = {"t": 0.0}
        eng, rec, _ = _latency_engine(clock)
        # 95s of healthy traffic, snapshotted along the way
        for step in range(10):
            for _ in range(1000):
                rec.record("decide", 0.001)
            clock["t"] = (step + 1) * 9.5
            eng.evaluate()
        # sharp regression SINCE the last snapshot: the fast window's
        # baseline is the t=95 snapshot so it sees ~100% violations; the
        # slow window's baseline is ~90s older and dilutes them under
        # 9000 healthy events
        for _ in range(60):
            rec.record("decide", 0.5)
        clock["t"] += 10.5
        results = eng.evaluate()
        decide = results["decide"]
        assert decide["fast"]["burn"] > 14.4
        assert decide["slow"]["burn"] < 6.0
        assert not decide["tripped"] and eng.tripped() == []

    def test_sustained_regression_trips_and_recovers(self):
        clock = {"t": 0.0}
        eng, rec, _ = _latency_engine(clock)
        fired = []
        eng.on_trip.append(lambda name, detail: fired.append(name))
        for _ in range(100):
            rec.record("decide", 0.001)
        eng.evaluate()
        # sustained: violations dominate BOTH windows
        for step in range(12):
            for _ in range(50):
                rec.record("decide", 0.5)
            clock["t"] += 10.0
            eng.evaluate()
        assert eng.tripped() == ["decide"]
        assert fired == ["decide"], "rising edge must fire exactly once"
        assert eng.trip_counts["decide"] == 1
        # recovery: healthy traffic washes both windows out
        for step in range(30):
            for _ in range(2000):
                rec.record("decide", 0.001)
            clock["t"] += 10.0
            eng.evaluate()
        assert eng.tripped() == []

    def test_error_rate_objective(self):
        clock = {"t": 0.0}
        state = {"sched": 0, "failed": 0}
        eng = SloEngine(
            [SloObjective(
                name="binds", kind="error_rate",
                numerator="failed_bindings",
                denominator="total_scheduled", budget=0.05,
                fast_burn_threshold=2.0, slow_burn_threshold=2.0,
            )],
            lambda: {
                "total_scheduled": state["sched"],
                "failed_bindings": state["failed"],
            },
            fast_window_s=10.0, slow_window_s=20.0,
            clock=lambda: clock["t"],
        )
        eng.evaluate()
        state["sched"] = 100
        state["failed"] = 50  # 50% failures vs 5% budget = 10x burn
        clock["t"] = 30.0
        results = eng.evaluate()
        assert results["binds"]["fast"]["burn"] == pytest.approx(10.0)
        assert results["binds"]["tripped"]

    def test_throughput_floor_objective(self):
        clock = {"t": 0.0}
        state = {"n": 0}
        eng = SloEngine(
            [SloObjective(
                name="floor", kind="throughput",
                counter="total_scheduled", min_per_s=10.0,
            )],
            lambda: {"total_scheduled": state["n"]},
            fast_window_s=10.0, slow_window_s=20.0,
            clock=lambda: clock["t"],
        )
        eng.evaluate()
        state["n"] = 400  # 40/s over 10s >> 10/s floor
        clock["t"] = 10.0
        results = eng.evaluate()
        assert results["floor"]["fast"]["burn"] == pytest.approx(0.25)
        assert not results["floor"]["tripped"]
        state["n"] = 410  # 1/s over the next 10s: fast window misses...
        clock["t"] = 20.0
        results = eng.evaluate()
        assert results["floor"]["fast"]["burn"] > 1.0
        # ...but the slow window still averages above the floor: no trip
        # (the multiwindow pairing working as designed)
        assert not results["floor"]["tripped"]
        state["n"] = 412  # sustained starvation: both windows miss
        clock["t"] = 30.0
        results = eng.evaluate()
        assert results["floor"]["fast"]["burn"] > 1.0
        assert results["floor"]["slow"]["burn"] > 1.0
        assert results["floor"]["tripped"]

    def test_missing_stat_paths_read_zero(self):
        clock = {"t": 0.0}
        eng = SloEngine(
            [SloObjective(
                name="e", kind="error_rate", numerator="nope.deep",
                denominator="also.nope", budget=0.1,
            )],
            lambda: {}, clock=lambda: clock["t"],
        )
        eng.evaluate()
        clock["t"] = 400.0
        results = eng.evaluate()  # must not raise
        assert results["e"]["fast"]["burn"] == 0.0


class TestSurfaces:
    def test_gauges_and_snapshot(self):
        clock = {"t": 0.0}
        eng, rec, _ = _latency_engine(clock)
        rec.record("decide", 0.001)
        eng.evaluate()
        clock["t"] = 50.0
        eng.evaluate()
        gauges = eng.gauges()
        assert gauges["decide_fast_burn"] == 0.0
        assert gauges["decide_tripped"] is False
        snap = eng.snapshot()
        assert snap["objectives"]["decide"]["kind"] == "latency"
        assert snap["evaluations"] == 2

    def test_debug_slo_endpoint_and_metrics_gauges(self):
        from k8s_llm_scheduler_tpu.observability.metrics import MetricsServer

        clock = {"t": 0.0}
        eng, rec, _ = _latency_engine(clock)
        rec.record("decide", 0.001)
        eng.evaluate()
        server = MetricsServer(
            lambda: {}, port=0, host="127.0.0.1", slo_engine=eng,
        )
        server.start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            body = json.loads(
                urllib.request.urlopen(f"{base}/debug/slo").read()
            )
            assert "decide" in body["objectives"]
            text = urllib.request.urlopen(f"{base}/metrics").read().decode()
            assert "llm_scheduler_slo_decide_tripped" in text
        finally:
            server.stop()

    def test_breaker_advisory_records_without_state_change(self):
        breaker = CircuitBreaker(failure_threshold=2)
        clock = {"t": 0.0}
        eng, rec, _ = _latency_engine(clock)
        eng.on_trip.append(lambda name, _d: breaker.slo_advisory(name))
        for _ in range(10):
            rec.record("decide", 0.001)
        eng.evaluate()
        for step in range(12):
            for _ in range(50):
                rec.record("decide", 0.5)
            clock["t"] += 10.0
            eng.evaluate()
        stats = breaker.stats()
        assert stats["slo_advisories"] == 1
        assert stats["last_slo_trip"] == "decide"
        assert stats["state"] == "closed"  # advisory, never a transition


class TestTickerLifecycle:
    """The background ticker under repeated controller restarts: a
    double start must never leak a second thread, and stop must join
    exactly once no matter how many owners call it (MetricsServer.stop
    and the CLI shutdown path both do)."""

    def _engine(self):
        return SloEngine(
            [SloObjective(name="o", kind="throughput", min_per_s=1.0)],
            lambda: {"total_scheduled": 0},
        )

    def _slo_threads(self):
        import threading

        return [
            t for t in threading.enumerate() if t.name == "slo-engine"
        ]

    def test_double_start_keeps_one_thread(self):
        eng = self._engine()
        eng.start(interval_s=60.0)
        first = eng._thread
        for _ in range(5):
            eng.start(interval_s=60.0)
        try:
            assert eng._thread is first
            assert len(self._slo_threads()) == 1
        finally:
            eng.stop()

    def test_stop_is_idempotent_and_joins_once(self):
        eng = self._engine()
        eng.start(interval_s=60.0)
        thread = eng._thread
        eng.stop()
        assert not thread.is_alive()
        assert eng._thread is None
        eng.stop()  # second owner: no-op, no error
        assert self._slo_threads() == []

    def test_restart_cycle_leaks_no_threads(self):
        eng = self._engine()
        for _ in range(4):
            eng.start(interval_s=60.0)
            eng.stop()
        assert self._slo_threads() == []
        # restartable: a fresh start after the cycles still ticks
        eng.start(interval_s=60.0)
        try:
            assert len(self._slo_threads()) == 1
        finally:
            eng.stop()

    def test_concurrent_starts_spawn_exactly_one_thread(self):
        import threading

        eng = self._engine()
        barrier = threading.Barrier(8)

        def racer():
            barrier.wait()
            eng.start(interval_s=60.0)

        racers = [threading.Thread(target=racer) for _ in range(8)]
        for t in racers:
            t.start()
        for t in racers:
            t.join()
        try:
            assert len(self._slo_threads()) == 1
        finally:
            eng.stop()
        assert self._slo_threads() == []

    def test_metrics_server_stop_joins_ticker(self):
        from k8s_llm_scheduler_tpu.observability.metrics import (
            MetricsServer,
        )

        eng = self._engine()
        eng.start(interval_s=60.0)
        server = MetricsServer(
            lambda: {}, port=0, host="127.0.0.1", slo_engine=eng,
        )
        server.start()
        server.stop()
        assert self._slo_threads() == []
        eng.stop()  # the owner's own teardown is still safe


class TestCanaryIntegration:
    """Acceptance path: latency regression -> SLO trip -> an OPEN canary
    burn-in rolls back immediately (rollout/canary.py slo_engine input)."""

    class FakeRegistry:
        def __init__(self):
            self.active_version = 1
            self.scores = {}

        def active(self):
            return self.active_version

        def set_active(self, v):
            self.active_version = v

        def versions(self):
            return [1, 2]

        def record_scores(self, version, scores):
            self.scores.setdefault(version, {}).update(scores)

    class FakeSwapper:
        def __init__(self):
            self.calls = []

        def swap_to(self, version):
            self.calls.append(version)
            return {"version": version, "pause_s": 0.0}

    def test_slo_trip_rolls_back_open_burn_in(self):
        from k8s_llm_scheduler_tpu.rollout.canary import CanaryController

        clock = {"t": 0.0}
        eng, rec, state = _latency_engine(clock)
        registry = self.FakeRegistry()
        swapper = self.FakeSwapper()
        controller = CanaryController(
            registry, swapper,
            stats_provider=lambda: {
                "llm_decisions": state["scheduled"], "cache_decisions": 0,
                "fallback_decisions": 0, "failed_bindings": 0,
                "client": {"invalid_decisions": 0},
            },
            gate_runner=lambda v: {"pass": True, "checks": {}},
            burn_in_decisions=10_000,  # the count window NEVER fills
            slo_engine=eng,
        )
        for _ in range(100):
            rec.record("decide", 0.001)
        eng.evaluate()
        assert controller.tick()["action"] == "promoted"
        assert swapper.calls == [2]
        # healthy while the SLO holds: burn-in stays open
        assert controller.tick() is None
        # synthetic latency regression, sustained across both windows
        for step in range(12):
            for _ in range(50):
                rec.record("decide", 0.5)
            clock["t"] += 10.0
            eng.evaluate()
        assert eng.tripped() == ["decide"]
        # the open burn-in trips on the SLO signal, NOT on decision count
        assert controller.tick() == "rolled_back"
        assert swapper.calls == [2, 1]
        assert registry.active() == 1
        assert 2 in controller.rejected
        burn = registry.scores[2]["burn_in"]
        assert burn["tripped"] == ["slo:decide"]
        assert burn["rates"]["slo_tripped"] == ["decide"]
