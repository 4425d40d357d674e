"""Deterministic chaos plane (chaos/): seeded fault schedules, the
runtime invariant monitor, deadline-budgeted degradation, breaker
cooldown jitter, and the wave-barriered chaos harness end to end over
the real stack — same seed, same fault schedule, byte-identical trace.
"""

import asyncio
import json
import logging
import time

import pytest

from k8s_llm_scheduler_tpu.chaos import (
    REGIMES,
    ChaosBackend,
    FaultEvent,
    FaultInjector,
    FaultPlan,
    InvariantMonitor,
    build_chaos_trace,
    run_chaos,
    save_chaos_trace,
    verify_chaos_trace,
)
from k8s_llm_scheduler_tpu.chaos.faults import stable_fraction
from k8s_llm_scheduler_tpu.chaos.harness import canonical_chaos_bytes
from k8s_llm_scheduler_tpu.core.breaker import CircuitBreaker, CircuitState
from k8s_llm_scheduler_tpu.engine.backend import BackendError, StubBackend
from k8s_llm_scheduler_tpu.sched import deadline
from k8s_llm_scheduler_tpu.sched.client import DecisionClient
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

logging.getLogger("k8s_llm_scheduler_tpu").setLevel(logging.CRITICAL)


class FakeClock:
    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def make_nodes(n=3):
    return [
        NodeMetrics(
            name=f"node-{i}", cpu_usage_percent=10.0 * (i + 1),
            memory_usage_percent=10.0 * (i + 1), available_cpu_cores=8.0,
            available_memory_gb=32.0, pod_count=i, max_pods=110,
            labels={}, taints=(), conditions={"Ready": "True"},
        )
        for i in range(n)
    ]


def make_pod(i=0):
    return PodSpec(
        name=f"p{i}", namespace="default", cpu_request=0.1,
        memory_request=0.125, node_selector={}, tolerations=(), priority=0,
    )


# ---------------------------------------------------------------- FaultPlan
class TestFaultPlan:
    def test_same_seed_same_plan(self):
        for regime in REGIMES:
            a = FaultPlan.generate(regime, 7, 8)
            b = FaultPlan.generate(regime, 7, 8)
            assert a == b
            assert a.digest() == b.digest()

    def test_different_seed_different_plan_where_rng_used(self):
        # node-failure draws its victim cohort from the rng
        a = FaultPlan.generate("node-failure", 0, 8, n_nodes=12)
        b = FaultPlan.generate("node-failure", 1, 8, n_nodes=12)
        assert a.churn != b.churn

    def test_round_trips_through_dict(self):
        plan = FaultPlan.generate("wire-flaky", 3, 9)
        again = FaultPlan.from_dict(plan.to_dict())
        assert again == plan
        assert again.digest() == plan.digest()

    def test_unknown_regime_rejected(self):
        with pytest.raises(ValueError, match="unknown chaos regime"):
            FaultPlan.generate("nope", 0, 8)

    def test_too_few_waves_rejected(self):
        with pytest.raises(ValueError, match="n_waves >= 3"):
            FaultPlan.generate("brownout", 0, 2)

    def test_event_validation(self):
        with pytest.raises(ValueError, match="unknown seam"):
            FaultEvent("nope", "reset", 0, 1)
        with pytest.raises(ValueError, match="no fault kind"):
            FaultEvent("wire", "gone_410", 0, 1)
        with pytest.raises(ValueError, match="empty fault window"):
            FaultEvent("wire", "reset", 2, 2)

    def test_last_fault_wave_covers_churn(self):
        plan = FaultPlan.generate("node-failure", 0, 9)
        assert plan.last_fault_wave() >= max(
            c["wave"] for c in plan.churn if c["kind"] == "fail"
        )

    def test_every_regime_declares_a_known_mode(self):
        for name, info in REGIMES.items():
            assert info["mode"] in (
                "single", "wire", "fleet", "autoscale", "crash",
            ), name

    def test_every_regime_generates_at_minimum_waves(self):
        # regression: staged windows (410 then 5xx; renewals then
        # partition; reset then dup/delay) collapsed to EMPTY windows at
        # the documented n_waves floor and generate() raised
        for regime in REGIMES:
            for n_waves in (3, 4, 5):
                plan = FaultPlan.generate(regime, 0, n_waves)
                assert plan.events, (regime, n_waves)
                assert plan.last_fault_wave() < n_waves


class TestSeams:
    def _injector(self, *events):
        plan = FaultPlan(
            regime="wire-flaky", seed=0, n_waves=8, events=tuple(events)
        )
        return FaultInjector(plan)

    def test_window_gating_by_wave(self):
        inj = self._injector(FaultEvent("wire", "reset", 2, 4))
        seam = inj.seam("wire")
        for wave, expect in ((-1, False), (1, False), (2, True),
                            (3, True), (4, False)):
            inj.begin_wave(wave)
            assert (seam.should("reset") is not None) is expect, wave

    def test_fraction_picks_stable_victims(self):
        inj = self._injector(
            FaultEvent("wire", "reset", 0, 1, (("fraction", 0.5),))
        )
        inj.begin_wave(0)
        seam = inj.seam("wire")
        keys = [f"pod-{i}" for i in range(100)]
        victims = {k for k in keys if seam.should("reset", key=k)}
        assert 20 < len(victims) < 80           # the hash actually splits
        again = {k for k in keys if seam.should("reset", key=k)}
        assert victims == again                  # and stably

    def test_holder_param_scopes_the_fault(self):
        inj = self._injector(
            FaultEvent("lease", "partition", 0, 1, (("holder", "r0"),))
        )
        inj.begin_wave(0)
        seam = inj.seam("lease")
        assert seam.should("partition", key="r0") is not None
        assert seam.should("partition", key="r1") is None

    def test_times_budget_caps_firings(self):
        inj = self._injector(
            FaultEvent("watch", "api_5xx", 0, 4, (("times", 3),))
        )
        inj.begin_wave(0)
        seam = inj.seam("watch")
        fired = sum(
            1 for _ in range(10) if seam.should("api_5xx") is not None
        )
        assert fired == 3
        assert inj.injection_counts() == {"watch.api_5xx": 3}

    def test_stable_fraction_is_cross_run_stable(self):
        # pinned value: blake2b, not hash() — MUST NOT vary with
        # PYTHONHASHSEED or process
        assert stable_fraction("wire:reset:pod-1") == pytest.approx(
            stable_fraction("wire:reset:pod-1")
        )
        assert 0.0 <= stable_fraction("x") < 1.0


class TestChaosBackend:
    def test_error_and_slow_and_malformed_by_pod(self):
        plan = FaultPlan(
            regime="circuit-open", seed=0, n_waves=8,
            events=(
                FaultEvent("backend", "error", 0, 1),
                FaultEvent("backend", "malformed", 1, 2),
            ),
        )
        inj = FaultInjector(plan)
        sleeps = []
        backend = ChaosBackend(
            StubBackend(), inj.seam("backend"), sleep=sleeps.append
        )
        nodes = make_nodes()
        inj.begin_wave(0)
        with pytest.raises(BackendError, match="injected device failure"):
            backend.get_scheduling_decision(make_pod(), nodes)
        inj.begin_wave(1)
        decision = backend.get_scheduling_decision(make_pod(), nodes)
        assert decision.selected_node == "chaos-no-such-node"
        inj.begin_wave(5)  # quiet wave: passthrough
        decision = backend.get_scheduling_decision(make_pod(), nodes)
        assert decision.selected_node in {n.name for n in nodes}


# --------------------------------------------------------------- invariants
class _FakeStore:
    def __init__(self, holder):
        self._holder = holder

    def holder_of(self, shard):
        return self._holder


class TestInvariantMonitor:
    def test_double_bind_violation(self):
        mon = InvariantMonitor()
        mon.note_bind(True, "ns", "p", "node-0")
        assert mon.clean
        mon.note_bind(True, "ns", "p", "node-1")
        report = mon.report()
        assert not report["clean"]
        v = report["violations"][0]
        assert v["invariant"] == "exactly_once_bind"
        assert "node-0" in v["detail"] and "node-1" in v["detail"]

    def test_failed_bind_is_not_a_double(self):
        mon = InvariantMonitor()
        mon.note_bind(True, "ns", "p", "node-0")
        mon.note_bind(False, "ns", "p", "node-1")
        assert mon.clean
        assert ("ns", "p") in mon.attempted_pods()

    def test_bind_after_fence_violation(self):
        from k8s_llm_scheduler_tpu.fleet.lease import shard_of

        mon = InvariantMonitor()
        mon.note_bind(
            True, "ns", "p", "node-0",
            holder="replica-0", store=_FakeStore("replica-1"), n_shards=8,
        )
        report = mon.report()
        assert [v["invariant"] for v in report["violations"]] == [
            "bind_after_fence"
        ]
        assert str(shard_of("ns", "p", 8)) in report["violations"][0]["detail"]

    def test_stale_generation_violation(self):
        # the monitor must catch a cache that REGRESSES to serving
        # pre-bump entries — model that bug with a generation-blind cache
        class _StaleCache:
            def __init__(self):
                self._d = {}
                self.generation = 0
                self.ttl_seconds = 300.0

            def get(self, pod, nodes, key=None):
                return self._d.get(key)

            def set(self, pod, nodes, decision, key=None, generation=None):
                self._d[key] = decision

            def bump_generation(self):
                self.generation += 1
                return self.generation

            def stats(self):
                return {}

        mon = InvariantMonitor()
        cache = mon.wrap_cache(_StaleCache())
        pod, nodes = make_pod(), make_nodes()
        decision = SchedulingDecision(
            selected_node="node-0", confidence=0.9, reasoning="t",
            source=DecisionSource.LLM,
        )
        cache.set(pod, nodes, decision)
        assert cache.get(pod, nodes) is not None and mon.clean
        cache.bump_generation()
        assert cache.get(pod, nodes) is not None   # the bug: stale serve
        report = mon.report()
        assert [v["invariant"] for v in report["violations"]] == [
            "stale_generation"
        ]

    def test_healthy_generation_stamped_cache_is_clean(self):
        from k8s_llm_scheduler_tpu.core.cache import DecisionCache

        mon = InvariantMonitor()
        cache = mon.wrap_cache(DecisionCache(ttl_seconds=300))
        pod, nodes = make_pod(), make_nodes()
        decision = SchedulingDecision(
            selected_node="node-0", confidence=0.9, reasoning="t",
            source=DecisionSource.LLM,
        )
        cache.set(pod, nodes, decision)
        assert cache.get(pod, nodes) is not None
        cache.bump_generation()
        # the real cache's generation-stamped keys MISS after a bump, so
        # no stale entry can be served and the monitor stays clean
        assert cache.get(pod, nodes) is None
        assert mon.clean

    def test_lost_pod_violation(self):
        mon = InvariantMonitor()
        mon.note_bind(True, "ns", "a", "node-0")
        mon.finalize(
            expected=[("ns", "a"), ("ns", "b"), ("ns", "c")],
            pending=[("ns", "b")],
        )
        report = mon.report()
        assert [v["invariant"] for v in report["violations"]] == ["lost_pod"]
        assert report["violations"][0]["subject"] == "ns/c"

    def test_breaker_edges_judged(self):
        mon = InvariantMonitor()
        breaker = CircuitBreaker(failure_threshold=1, timeout_seconds=60.0)
        mon.watch_breaker(breaker)
        breaker.record_failure()          # CLOSED -> OPEN: legal
        assert mon.clean
        breaker.on_transition(CircuitState.CLOSED, CircuitState.HALF_OPEN)
        report = mon.report()
        assert [v["invariant"] for v in report["violations"]] == [
            "breaker_transition"
        ]
        assert "closed -> half_open" in report["violations"][0]["detail"]

    def test_violation_carries_wave_stamp(self):
        plan = FaultPlan.generate("wire-flaky", 0, 6)
        inj = FaultInjector(plan)
        inj.begin_wave(3)
        mon = InvariantMonitor(inj)
        mon.note_bind(True, "ns", "p", "n0")
        mon.note_bind(True, "ns", "p", "n1")
        assert mon.report()["violations"][0]["wave"] == 3

    def test_violation_stamps_the_decision_trace(self):
        from k8s_llm_scheduler_tpu.observability import spans

        old_flight = spans.flight
        spans.flight = spans.FlightRecorder(capacity=16)
        spans.configure(enabled=True)
        try:
            mon = InvariantMonitor()
            with spans.start_trace("decision", pod="ns/p") as t:
                mon.note_bind(True, "ns", "p", "n0")
                mon.note_bind(True, "ns", "p", "n1")
                trace_id = t.trace_id
            v = mon.report()["violations"][0]
            assert v["trace_id"] == trace_id
            entry = spans.flight.get(trace_id)
            assert entry["meta"]["invariant_violation"] == "exactly_once_bind"
        finally:
            spans.flight = old_flight


# ----------------------------------------------------------------- deadline
class TestDeadlineBudget:
    def test_remaining_and_expiry_on_injected_clock(self):
        clock = FakeClock()
        budget = DeadlineBudget.start(100.0, clock=clock)
        assert budget.remaining_ms() == pytest.approx(100.0)
        clock.advance(0.06)
        assert budget.remaining_ms() == pytest.approx(40.0)
        assert not budget.expired
        clock.advance(0.05)
        assert budget.expired

    def test_ambient_install(self):
        assert deadline.current_budget() is None
        clock = FakeClock()
        budget = DeadlineBudget.start(200.0, clock=clock)
        with deadline.running(budget):
            assert deadline.current_budget() is budget
            assert deadline.remaining_ms() == pytest.approx(200.0)
            # what a worker reconstructs from the frame's deadline_ms:
            # a fresh budget started from the sender's remainder
            clock.advance(0.05)
            wire = DeadlineBudget.start(
                deadline.remaining_ms(), clock=clock
            )
            assert wire.remaining_ms() == pytest.approx(150.0)
        assert deadline.current_budget() is None
        with deadline.running(None):
            assert deadline.remaining_ms() is None


class _SlowBackend:
    def __init__(self, delay_s=0.0, fail=False):
        self.delay_s = delay_s
        self.fail = fail
        self.calls = 0

    async def get_scheduling_decision_async(self, pod, nodes):
        self.calls += 1
        if self.delay_s:
            await asyncio.sleep(self.delay_s)
        if self.fail:
            raise BackendError("down")
        return SchedulingDecision(
            selected_node=nodes[0].name, confidence=0.9, reasoning="t",
            source=DecisionSource.LLM,
        )


class TestDeadlineLadder:
    async def test_exhausted_budget_sheds_without_calling_backend(self):
        backend = _SlowBackend()
        client = DecisionClient(
            backend, cache=None, breaker=None,
            deadline_ms=0.001, llm_min_budget_ms=25.0,
        )
        decision = await client.get_scheduling_decision(
            make_pod(), make_nodes()
        )
        assert decision is not None and decision.fallback_needed
        assert backend.calls == 0                   # never reached the model
        assert client.stats["degraded_decisions"] == 1

    async def test_slow_backend_times_out_and_degrades(self):
        backend = _SlowBackend(delay_s=0.5)
        client = DecisionClient(
            backend, cache=None, breaker=None,
            deadline_ms=60.0, llm_min_budget_ms=1.0,
        )
        t0 = time.perf_counter()
        decision = await client.get_scheduling_decision(
            make_pod(), make_nodes()
        )
        assert (time.perf_counter() - t0) < 0.4     # shed, not waited out
        assert decision is not None and decision.fallback_needed
        assert client.stats["deadline_timeouts"] == 1
        assert client.stats["degraded_decisions"] == 1

    async def test_deadline_shed_does_not_count_breaker_failure(self):
        breaker = CircuitBreaker(failure_threshold=1, timeout_seconds=60.0)
        client = DecisionClient(
            _SlowBackend(delay_s=0.5), cache=None, breaker=breaker,
            deadline_ms=60.0, llm_min_budget_ms=1.0,
        )
        await client.get_scheduling_decision(make_pod(), make_nodes())
        assert breaker.state is CircuitState.CLOSED  # caller load != sick device

    async def test_budget_caps_retry_backoff(self):
        backend = _SlowBackend(fail=True)
        client = DecisionClient(
            backend, cache=None, breaker=None,
            max_retries=3, retry_delay=30.0,        # absurd backoff...
            deadline_ms=80.0, llm_min_budget_ms=1.0,
        )
        t0 = time.perf_counter()
        decision = await client.get_scheduling_decision(
            make_pod(), make_nodes()
        )
        # ...must be capped by the budget, not waited out
        assert (time.perf_counter() - t0) < 2.0
        assert decision is not None and decision.fallback_needed

    async def test_brownout_sheds_and_clears(self):
        backend = _SlowBackend()
        client = DecisionClient(backend, cache=None, breaker=None)
        client.enter_brownout("slo:decide_p99")
        decision = await client.get_scheduling_decision(
            make_pod(), make_nodes()
        )
        assert decision.fallback_needed and backend.calls == 0
        assert client.stats["brownout_decisions"] == 1
        assert client.get_stats()["brownout"] == ["slo:decide_p99"]
        # reasons are a SET: both burns must clear
        client.enter_brownout("slo:error_rate")
        client.exit_brownout("slo:decide_p99")
        assert client.brownout
        client.exit_brownout("slo:error_rate")
        assert not client.brownout
        await client.get_scheduling_decision(make_pod(), make_nodes())
        assert backend.calls == 1

    def test_wire_refuses_expired_frame(self):
        from k8s_llm_scheduler_tpu.sched.replica import (
            ReplicaClient,
            ReplicaServer,
        )

        srv = ReplicaServer(StubBackend(), host="127.0.0.1", port=0)
        client = ReplicaClient("127.0.0.1", srv.port)
        try:
            budget = DeadlineBudget.start(-5.0)  # already expired
            with deadline.running(budget):
                with pytest.raises(DeadlineExceededError):
                    client.get_scheduling_decision(make_pod(), make_nodes())
            # a healthy budget rides the frame and the decision lands
            with deadline.running(DeadlineBudget.start(5000.0)):
                decision = client.get_scheduling_decision(
                    make_pod(), make_nodes()
                )
            assert decision.selected_node
        finally:
            client.close()
            srv.close()


# ------------------------------------------------------------ breaker jitter
class TestBreakerCooldownJitter:
    def test_fleet_replicas_do_not_probe_in_lockstep(self):
        """Satellite regression: N replicas tripping on one dead backend
        at the same instant must NOT all reach HALF_OPEN at the same
        instant once the shared cooldown elapses."""
        import random

        clock = FakeClock()
        breakers = [
            CircuitBreaker(
                failure_threshold=1, timeout_seconds=10.0,
                cooldown_jitter=0.5, clock=clock,
                jitter_rng=random.Random(i),
            )
            for i in range(8)
        ]
        for b in breakers:
            b.record_failure()                    # all trip at t=1000
            assert b.state is CircuitState.OPEN
        cooldowns = {b.stats()["cooldown_s"] for b in breakers}
        assert len(cooldowns) >= 6                # drawn apart, not shared
        clock.advance(10.0)                       # the UN-jittered cooldown
        states = [b.state for b in breakers]
        half_open = [s for s in states if s is CircuitState.HALF_OPEN]
        # jitter holds most replicas back past the base cooldown
        assert 0 < len(half_open) < len(breakers) or not half_open
        clock.advance(5.1)                        # past max jitter (50%)
        assert all(b.state is CircuitState.HALF_OPEN for b in breakers)

    def test_zero_jitter_keeps_exact_cooldown(self):
        clock = FakeClock()
        breaker = CircuitBreaker(
            failure_threshold=1, timeout_seconds=10.0,
            cooldown_jitter=0.0, clock=clock,
        )
        breaker.record_failure()
        assert breaker.stats()["cooldown_s"] == 10.0
        clock.advance(9.99)
        assert breaker.state is CircuitState.OPEN
        clock.advance(0.02)
        assert breaker.state is CircuitState.HALF_OPEN

    def test_each_trip_redraws_the_cooldown(self):
        import random

        clock = FakeClock()
        breaker = CircuitBreaker(
            failure_threshold=1, timeout_seconds=10.0,
            cooldown_jitter=0.5, clock=clock, jitter_rng=random.Random(7),
        )
        draws = set()
        for _ in range(5):
            breaker.record_failure()
            draws.add(breaker.stats()["cooldown_s"])
            clock.advance(20.0)
            assert breaker.state is CircuitState.HALF_OPEN
            breaker.record_success()
        assert len(draws) >= 4
        assert all(10.0 <= d <= 15.0 for d in draws)

    def test_transition_hook_sees_legal_walk(self):
        clock = FakeClock()
        breaker = CircuitBreaker(
            failure_threshold=1, timeout_seconds=10.0, cooldown_jitter=0.0,
            clock=clock,
        )
        edges = []
        breaker.on_transition = lambda old, new: edges.append(
            (old.value, new.value)
        )
        breaker.record_failure()
        clock.advance(10.1)
        _ = breaker.state
        breaker.record_success()
        assert edges == [
            ("closed", "open"), ("open", "half_open"),
            ("half_open", "closed"),
        ]


# ------------------------------------------------------------------ harness
class TestChaosSmoke:
    """Fast-tier seeded chaos smoke: one single-mode regime, small plan,
    real wire-fake stack, <10s wall clock."""

    def test_node_failure_smoke_is_clean_and_bounded(self):
        t0 = time.perf_counter()
        report = run_chaos(
            "node-failure", seed=0, n_waves=4, n_nodes=6, n_pods=18,
            wave_timeout_s=15.0, quality=False,
        )
        elapsed = time.perf_counter() - t0
        assert elapsed < 10.0, f"chaos smoke took {elapsed:.1f}s"
        assert report["invariants"]["clean"], report["invariants"]
        assert report["scores"]["bound_frac"] == 1.0
        assert report["invariants"]["checks"]["exactly_once_bind"] == 18
        # the fault actually fired
        assert report["injections"].get("backend.slow", 0) >= 1

    def test_smoke_trace_is_deterministic_and_replayable(self, tmp_path):
        kwargs = dict(
            seed=11, n_waves=4, n_nodes=6, n_pods=18,
            wave_timeout_s=15.0, quality=False,
        )
        r1 = run_chaos("node-failure", **kwargs)
        r2 = run_chaos("node-failure", **kwargs)
        b1 = canonical_chaos_bytes(build_chaos_trace(r1))
        b2 = canonical_chaos_bytes(build_chaos_trace(r2))
        assert b1 == b2                       # same seed -> same bytes
        path = tmp_path / "chaos.trace"
        save_chaos_trace(r1, path)
        ok, detail = verify_chaos_trace(path)
        assert ok, detail

    def test_tampered_trace_is_rejected(self, tmp_path):
        report = run_chaos(
            "node-failure", seed=11, n_waves=4, n_nodes=6, n_pods=18,
            wave_timeout_s=15.0, quality=False,
        )
        path = tmp_path / "chaos.trace"
        save_chaos_trace(report, path)
        trace = json.loads(path.read_bytes())
        # tamper 1: move a placement
        victim = sorted(trace["placements"])[0]
        trace["placements"][victim] = "sim-node-000" \
            if trace["placements"][victim] != "sim-node-000" else "sim-node-001"
        path.write_bytes(json.dumps(trace).encode())
        ok, detail = verify_chaos_trace(path)
        assert not ok and "diverged" in detail
        # tamper 2: forge the fault schedule itself
        trace = json.loads(save_and_load(report))
        trace["plan"]["events"][0]["start_wave"] += 1
        path.write_bytes(json.dumps(trace).encode())
        with pytest.raises(Exception, match="fault schedule diverged"):
            verify_chaos_trace(path)

    def test_brownout_regime_engages_the_ladder(self):
        report = run_chaos(
            "brownout", seed=2, n_waves=5, n_nodes=6, n_pods=20,
            wave_timeout_s=15.0, quality=False,
        )
        assert report["invariants"]["clean"]
        # acceptance: the degraded-decision fraction is >0 in the
        # brownout regime — the ladder actually engaged
        assert report["degraded_fraction"] > 0
        assert report["scores"]["bound_frac"] == 1.0  # shed quality, not delivery
        assert report["client"]["brownout_decisions"] > 0

    def test_circuit_open_regime_trips_and_recovers(self):
        report = run_chaos(
            "circuit-open", seed=3, n_waves=5, n_nodes=6, n_pods=20,
            wave_timeout_s=15.0, quality=False,
        )
        assert report["invariants"]["clean"]
        assert report["client"]["circuit_breaker"]["trips"] >= 1
        assert report["scores"]["bound_frac"] == 1.0
        assert report["recovery"]["recovery_waves"] is not None
        # breaker walked legal edges under observation the whole run
        assert report["invariants"]["checks"]["breaker_transition"] >= 2


class TestLearnSwapRegime:
    """PR-level loop test for the `_signals` brownout subtraction: a hot
    swap opens a REAL CanaryController burn-in mid-run while an SLO
    brownout sheds decisions through the whole window — the burn-in must
    close clean (a brownout overlapping a burn-in must never roll back a
    healthy candidate), with the invariant monitor watching the swap's
    cache-generation bump the whole time."""

    def test_burn_in_survives_brownout_and_stays_clean(self):
        report = run_chaos(
            "learn-swap", seed=3, n_waves=6, n_nodes=8, n_pods=48,
            wave_timeout_s=15.0, quality=False,
        )
        assert report["invariants"]["clean"], report["invariants"]
        canary = report["canary"]
        assert canary["promotions"] == 1
        # the healthy candidate SURVIVED: burn-in closed "ok", zero
        # rollbacks — the brownout's degraded sheds were subtracted from
        # the fallback-rate trip (rollout/canary._signals)
        assert canary["result"] == "ok", canary
        assert canary["rollbacks"] == 0
        # the brownout genuinely overlapped the open burn-in
        assert report["degraded_fraction"] > 0
        assert report["injections"].get("swap.hot_swap", 0) == 1
        assert report["injections"].get("slo.brownout", 0) >= 1
        # every pod still bound exactly once under monitor observation
        # (the swap's generation bump can't strand or double-bind work)
        assert report["invariants"]["checks"]["exactly_once_bind"] == 48
        assert report["scores"]["bound_frac"] == 1.0

    def test_regime_trace_replays_byte_identically(self, tmp_path):
        kwargs = dict(
            seed=7, n_waves=6, n_nodes=8, n_pods=48,
            wave_timeout_s=15.0, quality=False,
        )
        r1 = run_chaos("learn-swap", **kwargs)
        r2 = run_chaos("learn-swap", **kwargs)
        assert (
            canonical_chaos_bytes(build_chaos_trace(r1))
            == canonical_chaos_bytes(build_chaos_trace(r2))
        )
        path = tmp_path / "learn-swap.trace"
        save_chaos_trace(r1, path)
        ok, detail = verify_chaos_trace(path)
        assert ok, detail


def save_and_load(report) -> str:
    return canonical_chaos_bytes(build_chaos_trace(report)).decode()


@pytest.mark.slow
class TestChaosRegimesSlow:
    @pytest.mark.parametrize("regime", sorted(REGIMES))
    def test_regime_clean_and_deterministic(self, regime):
        kwargs = dict(
            seed=5, n_waves=6, n_nodes=8, n_pods=36,
            wave_timeout_s=30.0, quality=False,
        )
        r1 = run_chaos(regime, **kwargs)
        r2 = run_chaos(regime, **kwargs)
        assert r1["invariants"]["clean"], r1["invariants"]["violations"]
        assert canonical_chaos_bytes(build_chaos_trace(r1)) == \
            canonical_chaos_bytes(build_chaos_trace(r2))

    def test_partition_regime_fences_and_fails_over(self):
        report = run_chaos(
            "partition", seed=0, n_waves=6, n_nodes=8, n_pods=36,
            quality=False,
        )
        assert report["invariants"]["clean"]
        assert report["scores"]["bound_frac"] == 1.0
        assert report["injections"].get("lease.partition", 0) >= 1
        assert report["injections"].get("lease.lost_renewal", 0) >= 1

    def test_clock_skew_regime_keeps_exactly_once(self):
        report = run_chaos(
            "clock-skew", seed=0, n_waves=6, n_nodes=8, n_pods=36,
            quality=False,
        )
        assert report["invariants"]["clean"]
        assert report["scores"]["bound_frac"] == 1.0
        assert report["injections"].get("lease.clock_skew", 0) >= 1

    def test_cache_outage_regime_serves_through_l1(self):
        report = run_chaos(
            "cache-outage", seed=0, n_waves=6, n_nodes=8, n_pods=36,
            quality=False,
        )
        assert report["invariants"]["clean"]
        assert report["scores"]["bound_frac"] == 1.0
        assert report["injections"].get("cache.l2_down", 0) >= 1


# ------------------------------------------------- satellite: double re-list
class TestWatch410DuringRebind:
    async def test_410_relist_racing_rebind_does_not_double_decide(self):
        """Satellite: a watch fresh-start (410 Gone mid-burst) re-lists
        still-pending pods while a lease-failover rebind re-list is in
        flight — the two paths must not double-decide, and above all must
        not double-bind."""
        from k8s_llm_scheduler_tpu.cluster.fake import FakeCluster, FakeNode
        from k8s_llm_scheduler_tpu.fleet import Fleet

        cluster = FakeCluster()
        for i in range(4):
            cluster.add_node(FakeNode(name=f"node-{i}"))
        clock = FakeClock()
        fleet = Fleet(
            cluster, cluster, lambda i: StubBackend(),
            n_replicas=2, n_shards=8, lease_ttl_s=5.0, clock=clock,
            list_pending=lambda: cluster.pending_pods("ai-llama-scheduler"),
        )
        mon = InvariantMonitor()
        for replica in fleet.replicas:
            replica.scheduler.binder = mon.wrap_binder(
                replica.scheduler.binder
            )
        await fleet.start(lease_threads=False)
        try:
            # replica-0 dies holding shards with pending pods
            dead = set(fleet.replicas[0].manager.owned())
            await fleet.kill_replica(0)
            from k8s_llm_scheduler_tpu.cluster.interface import RawPod
            from k8s_llm_scheduler_tpu.fleet.lease import shard_of

            pods = [
                RawPod(
                    name=f"orphan-{i}", namespace="default",
                    scheduler_name="ai-llama-scheduler",
                    container_requests=({"cpu": "100m", "memory": "128Mi"},),
                )
                for i in range(24)
            ]
            for p in pods:
                cluster.add_pod(p)
            orphans = [
                p for p in pods
                if shard_of(p.namespace, p.name, 8) in dead
            ]
            assert orphans
            survivor = fleet.replicas[1]
            # failover: the survivor claims the dead shards (rebind
            # re-list #1 fires on_gain)...
            clock.advance(6.0)
            gained, _lost = survivor.manager.tick()
            assert gained
            # ...while a 410-style watch fresh-start re-list lands AT THE
            # SAME TIME: schedule every still-pending pod again (this is
            # exactly what sched/loop does after a watch fresh start)
            relist = [
                asyncio.ensure_future(survivor.scheduler.schedule_pod(p))
                for p in cluster.pending_pods("ai-llama-scheduler")
            ]
            await asyncio.gather(*relist, return_exceptions=True)
            deadline_t = time.monotonic() + 20.0
            while time.monotonic() < deadline_t:
                if len(mon.bound_pods()) >= len(pods):
                    break
                await asyncio.sleep(0.01)
        finally:
            await fleet.stop()
        assert mon.clean, mon.report()["violations"]
        bound = [n for _ns, n, _node in cluster.bindings]
        assert len(bound) == len(set(bound)) == len(pods)
        # the scheduler-level dedup did its job: nobody decided a pod
        # that was already in flight on the same replica
        assert cluster.bind_count == len(pods)


# ------------------------------------------- satellite: clock-skew fencing
class TestLeaseFencingUnderSkew:
    def test_slow_clock_holder_loses_lease_but_cannot_bind(self):
        from k8s_llm_scheduler_tpu.fleet.lease import LeaseStore

        plan = FaultPlan(
            regime="clock-skew", seed=0, n_waves=8,
            events=(FaultEvent(
                "lease", "clock_skew", 0, 8,
                (("holder", "slow"), ("skew_s", -4.0)),
            ),),
        )
        inj = FaultInjector(plan)
        inj.begin_wave(0)
        clock = FakeClock()
        store = LeaseStore(4, ttl_s=5.0, clock=clock)
        store.fault_seam = inj.seam("lease")
        lease = store.try_acquire(0, "slow")
        # the skewed holder renews — but judged 4s in the past, the
        # renewal only holds ~1s of real time
        clock.advance(2.0)
        store.renew(0, "slow", lease.epoch)
        clock.advance(2.0)
        # store clock: expired. The healthy peer claims under a NEW epoch
        assert store.holder_of(0) is None
        peer = store.try_acquire(0, "fast")
        assert peer is not None and peer.epoch == lease.epoch + 1
        # the slow holder's fencing token is now stale: check_fence (the
        # bind-time gate) refuses it, and its renewal raises
        assert store.check_fence(0, "slow", lease.epoch) is False
        assert store.check_fence(0, "fast", peer.epoch) is True
        from k8s_llm_scheduler_tpu.fleet.lease import LeaseExpired

        with pytest.raises(LeaseExpired):
            store.renew(0, "slow", lease.epoch)

    def test_fast_clock_holder_steals_only_with_epoch_bump(self):
        from k8s_llm_scheduler_tpu.fleet.lease import LeaseStore

        plan = FaultPlan(
            regime="clock-skew", seed=0, n_waves=8,
            events=(FaultEvent(
                "lease", "clock_skew", 0, 8,
                (("holder", "fast"), ("skew_s", 4.0)),
            ),),
        )
        inj = FaultInjector(plan)
        inj.begin_wave(0)
        clock = FakeClock()
        store = LeaseStore(4, ttl_s=5.0, clock=clock)
        store.fault_seam = inj.seam("lease")
        lease = store.try_acquire(0, "steady")
        clock.advance(2.0)
        # the fast-clock holder judges the live lease expired (now+4 >
        # expiry) and takes it — but ONLY under a bumped epoch, so the
        # steady holder is fenced, not double-bound
        stolen = store.try_acquire(0, "fast")
        assert stolen is not None and stolen.epoch == lease.epoch + 1
        assert store.check_fence(0, "steady", lease.epoch) is False


# ----------------------------------------------------------------- CLI + l2
class TestCacheOutageSeam:
    def test_l2_down_serves_l1_and_pauses_sync(self):
        from k8s_llm_scheduler_tpu.core.cache import DecisionCache
        from k8s_llm_scheduler_tpu.fleet.cache import TieredDecisionCache

        plan = FaultPlan(
            regime="cache-outage", seed=0, n_waves=8,
            events=(FaultEvent("cache", "l2_down", 1, 2),),
        )
        inj = FaultInjector(plan)
        l2 = DecisionCache(ttl_seconds=300)
        tiered = TieredDecisionCache(l2, l1_size=16)
        tiered.fault_seam = inj.seam("cache")
        pod, nodes = make_pod(), make_nodes()
        decision = SchedulingDecision(
            selected_node="node-0", confidence=0.9, reasoning="t",
            source=DecisionSource.LLM,
        )
        inj.begin_wave(0)
        tiered.set(pod, nodes, decision)
        assert tiered.get(pod, nodes) is not None    # healthy: L1 hit
        inj.begin_wave(1)                            # L2 goes dark
        assert tiered.get(pod, nodes) is not None    # L1 still serves
        # a DISTINCT shape (the cache is shape-keyed) written during the
        # outage must stay L1-only
        pod2 = PodSpec(
            name="p2", namespace="default", cpu_request=0.3,
            memory_request=0.5, node_selector={}, tolerations=(),
            priority=0,
        )
        tiered.set(pod2, nodes, decision)            # write is L1-only
        assert tiered.get(pod2, nodes) is not None
        assert l2.get(pod2, nodes) is None           # nothing reached L2
        assert tiered.stats()["l2_unavailable"] > 0
        inj.begin_wave(3)                            # recovery
        l2.bump_generation()                         # foreign bump while dark?
        assert tiered.get(pod, nodes) is None        # first sync invalidates


class TestChaosCli:
    def test_list_and_small_run_and_replay(self, tmp_path, capsys):
        from k8s_llm_scheduler_tpu.cli import main

        assert main(["chaos", "list"]) == 0
        out = capsys.readouterr().out
        for regime in REGIMES:
            assert regime in out

        trace_path = tmp_path / "run.trace"
        rc = main([
            "chaos", "run", "--regime", "node-failure", "--seed", "4",
            "--waves", "4", "--nodes", "6", "--pods", "18",
            "--trace", str(trace_path),
        ])
        assert rc == 0
        headline = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert headline["clean"] is True
        assert headline["regime"] == "node-failure"

        assert main(["chaos", "replay", str(trace_path)]) == 0
        replay = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert replay["ok"] is True and "bit-identical" in replay["detail"]
