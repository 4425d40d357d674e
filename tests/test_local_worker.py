"""LocalLLMBackend wave-worker scheduling policy, tested against a stub
engine (no jit, fast tier): wave batching, the ragged-tail hold deadline,
and pipelining while a wave is in flight."""

import json
import time
from types import SimpleNamespace

import pytest

from k8s_llm_scheduler_tpu.engine.local import LocalLLMBackend
from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer
from k8s_llm_scheduler_tpu.types import NodeMetrics, PodSpec


def make_nodes(n=3):
    return [
        NodeMetrics(
            name=f"node-{i}", cpu_usage_percent=10.0 * i,
            memory_usage_percent=10.0 * i, available_cpu_cores=8.0,
            available_memory_gb=32.0, pod_count=i, max_pods=110,
            labels={}, taints=(), conditions={"Ready": "True"},
        )
        for i in range(n)
    ]


def make_pod(i):
    return PodSpec(
        name=f"p{i}", namespace="default", cpu_request=0.1 + 0.01 * i,
        memory_request=0.125, node_selector={}, tolerations=(), priority=0,
    )


DECISION = json.dumps(
    {"selected_node": "node-1", "confidence": 0.9, "reasoning": "stub"}
)


class FakeHandle:
    def __init__(self, ready_at):
        self.ready_at = ready_at
        self.submitted_at = time.perf_counter()

    def is_ready(self):
        return time.perf_counter() >= self.ready_at


class FakeEngine:
    """Records submit times; each wave 'executes' for wave_s seconds."""

    max_slots = 4
    prefill_buckets = (4096,)

    def __init__(self, wave_s=0.25):
        self.wave_s = wave_s
        self.submits: list[tuple[float, int]] = []  # (t since init, n_rows)
        self.prefixes = 0
        self.grammars = 0
        self._t0 = time.perf_counter()

    def set_prefix(self, ids):
        self.prefixes += 1

    def set_grammar(self, dfa):
        self.grammars += 1

    def submit_wave(self, prompts, max_new_tokens):
        self.submits.append((time.perf_counter() - self._t0, len(prompts)))
        h = FakeHandle(time.perf_counter() + self.wave_s)
        h.n = len(prompts)
        return h

    def harvest_wave(self, h):
        # Models the real engine: a blocking harvest (device_get) returns
        # at the wave's TRUE completion regardless of what is_ready()
        # claims (an is_ready that flips late).
        while time.perf_counter() < h.ready_at:
            time.sleep(0.002)
        return [SimpleNamespace(text=DECISION) for _ in range(h.n)]

    def get_stats(self):
        return {}

    def prewarm_wave_siblings(self, limit=None):
        return 0  # idle prewarm: nothing to compile in a stub engine


class TestPrewarmUnderLoad:
    def test_prewarm_mid_burst_dropped_not_crashing(self):
        """A prewarm landing while a wave is in flight must resolve False
        and leave every real decision unharmed (regression: a prewarm
        item drained by the mid-tick coalescing/straggler loops used to
        reach submit_wave's len(suffix_ids) and fail the whole burst)."""
        eng = FakeEngine(wave_s=0.3)
        backend = LocalLLMBackend(
            eng, tokenizer=ByteTokenizer(), max_new_tokens=160,
            admit_wait_s=0.01,
        )
        try:
            nodes = make_nodes()
            import concurrent.futures as cf

            with cf.ThreadPoolExecutor(8) as pool:
                real = [
                    pool.submit(
                        backend.get_scheduling_decision, make_pod(i), nodes
                    )
                    for i in range(4)
                ]
                time.sleep(0.1)  # wave in flight (0.3s long)
                warm = backend.prewarm_prefix(make_nodes(4))
                # drop-or-install depends on when the drain lands relative
                # to the harvest; the regression is that it must RESOLVE
                # (not crash the worker) and leave every decision intact
                assert warm.result(timeout=5) in (False, True)
                for f in real:
                    assert f.result(timeout=10).selected_node == "node-1"
            # idle now: the same advisory installs
            assert backend.prewarm_prefix(make_nodes(4)).result(timeout=5)
        finally:
            backend.close()

    def test_busy_engine_drops_install_deterministically(self):
        """Unit-level: with a wave in flight, _submit_waves resolves the
        advisory False and leaves the current group untouched."""
        from collections import deque

        eng = FakeEngine()
        backend = LocalLLMBackend(eng, tokenizer=ByteTokenizer())
        try:
            item = backend._prepare_prewarm(make_nodes(3))
            waves = deque([(object(), [])])  # one wave "in flight"
            rest = backend._submit_waves([item], waves, [])
            assert rest == []
            assert item.future.result(timeout=1) is False
            assert backend._current_group is None
            assert eng.prefixes == 0
        finally:
            backend.close()

    def test_stale_prewarms_collapse_to_latest(self):
        eng = FakeEngine(wave_s=0.05)
        backend = LocalLLMBackend(
            eng, tokenizer=ByteTokenizer(), max_new_tokens=160,
        )
        try:
            futs = [backend.prewarm_prefix(make_nodes(2 + i)) for i in range(3)]
            results = [f.result(timeout=5) for f in futs]
            # the latest drained batch wins; earlier ones in the same tick
            # resolve False (drain timing may split them across ticks, in
            # which case each tick's survivor installs — all True is legal)
            assert results[-1] is True
            assert backend._current_group is not None
        finally:
            backend.close()


class LyingHandle(FakeHandle):
    """A handle whose is_ready NEVER fires — the failure mode where
    readiness tracks chain-drain, not this wave's completion."""

    def is_ready(self):
        return False


class TestHarvestDeadline:
    def test_lying_is_ready_still_resolves_at_wave_completion(self):
        """With is_ready never returning True, the worker must stop
        polling at the EMA deadline and harvest blockingly — decisions
        resolve around true wave completion instead of hanging behind the
        pipeline (r01-r05, earlier installation: wave-1 'ready' at 886ms
        vs true completion 469ms with 3 waves in flight)."""
        eng = FakeEngine(wave_s=0.3)

        orig_submit = eng.submit_wave

        def lying_submit(prompts, max_new_tokens):
            h = orig_submit(prompts, max_new_tokens)
            lying = LyingHandle(h.ready_at)
            lying.n = h.n
            return lying

        eng.submit_wave = lying_submit
        backend = LocalLLMBackend(
            eng, tokenizer=ByteTokenizer(), max_new_tokens=160,
            partial_hold_s=0.01, admit_wait_s=0.001,
        )
        try:
            nodes = make_nodes()
            t0 = time.perf_counter()
            decision = backend.get_scheduling_decision(make_pod(0), nodes)
            took = time.perf_counter() - t0
            assert decision.selected_node == "node-1"
            # ema starts at 0.5 -> deadline 0.25s, wave completes at 0.3s:
            # resolution ~0.3s, nowhere near the 60s request timeout the
            # old unbounded poll would have risked on a lying backend
            assert took < 1.5, f"decision took {took:.2f}s"
        finally:
            backend.close()


class TestPartialHoldDeadline:
    def test_held_tail_ships_before_wave_harvest(self):
        """A ragged tail arriving while a wave is in flight must submit
        once its hold deadline passes — not wait out the full wave round
        trip (round-3 fix: unbounded holds parked tails ~230 ms)."""
        eng = FakeEngine(wave_s=0.4)
        backend = LocalLLMBackend(
            eng, tokenizer=ByteTokenizer(), max_new_tokens=160,
            partial_hold_s=0.05, admit_wait_s=0.001,
        )
        try:
            nodes = make_nodes()
            import concurrent.futures as cf

            with cf.ThreadPoolExecutor(8) as pool:
                # full wave of 4 -> submits immediately. One batch call
                # (all four enqueued before any is awaited), not four pool
                # threads: on a loaded machine a late thread's row ships
                # as a held partial of its own, and behind TWO waves in
                # flight the tail rightly holds (TestTailHoldPolicy) — this
                # test is about the deadline behind ONE
                first = pool.submit(
                    backend.get_scheduling_decisions_batch,
                    [make_pod(i) for i in range(4)], nodes,
                )
                deadline = time.perf_counter() + 5
                while not eng.submits:
                    assert time.perf_counter() < deadline
                    time.sleep(0.002)
                assert eng.submits[0][1] == 4, eng.submits
                n_first = len(eng.submits)
                t_tail = time.perf_counter()
                tail = [
                    pool.submit(backend.get_scheduling_decision, make_pod(10 + i), nodes)
                    for i in range(2)
                ]
                for d in first.result(timeout=10) + [
                    f.result(timeout=10) for f in tail
                ]:
                    assert d.selected_node == "node-1"
            # the 2-row tail shipped after ~hold (0.05s), NOT after wave 1
            # finished (0.4s)
            tail_submits = eng.submits[n_first:]
            assert sum(n for _, n in tail_submits) == 2, eng.submits
            waited = tail_submits[-1][0] + eng._t0 - t_tail
            assert waited < 0.3, f"tail held {waited:.3f}s (deadline 0.05s)"
        finally:
            backend.close()

    def test_full_wave_submits_during_flight(self):
        """A FULL batch never holds: with wave 1 still executing, a second
        batch reaching max_slots rows pipelines immediately."""
        eng = FakeEngine(wave_s=0.4)
        backend = LocalLLMBackend(
            eng, tokenizer=ByteTokenizer(), max_new_tokens=160,
            partial_hold_s=10.0, admit_wait_s=0.01,
        )
        try:
            nodes = make_nodes()
            import concurrent.futures as cf

            with cf.ThreadPoolExecutor(8) as pool:
                first = [
                    pool.submit(backend.get_scheduling_decision, make_pod(i), nodes)
                    for i in range(4)
                ]
                time.sleep(0.1)  # wave(s) for batch 1 in flight (0.4s long)
                second = [
                    pool.submit(backend.get_scheduling_decision, make_pod(20 + i), nodes)
                    for i in range(4)
                ]
                for f in first + second:
                    assert f.result(timeout=10).selected_node == "node-1"
            # all 8 rows were submitted BEFORE the first wave's 0.4s flight
            # ended: a full second wave pipelines, it does not hold.
            first_done_at = eng.submits[0][0] + eng.wave_s
            rows_before = sum(n for t, n in eng.submits if t < first_done_at)
            assert rows_before == 8, eng.submits
        finally:
            backend.close()


class TestTailHoldPolicy:
    """run_group's hold rule, driven through _submit_waves directly with
    the in-flight deque planted (no timing): a ragged tail behind TWO or
    more waves holds however old it is — the device serves waves one
    after another, so it loses nothing, and a standing backlog otherwise
    recycles a 7+1 split for good; behind ONE wave the deadline rules;
    and a tail whose group the engine is about to leave ships."""

    @staticmethod
    def _items(backend, n, nodes, first=0, age_s=0.0):
        items = [
            backend._prepare_item(make_pod(first + i), nodes) for i in range(n)
        ]
        for item in items:
            item.enqueued_at -= age_s
        return items

    @pytest.mark.parametrize(
        "in_flight,age_s,ships",
        [
            (2, 5.0, False),   # old tail, deep pipeline: holds for company
            (5, 5.0, False),
            (1, 5.0, True),    # one wave left: past its deadline, ships
            (1, 0.0, False),   # one wave left, young: the 30 ms hold
            (0, 0.0, True),    # idle engine never holds
        ],
    )
    def test_ragged_tail_holds_behind_two_waves(self, in_flight, age_s, ships):
        from collections import deque

        eng = FakeEngine()
        backend = LocalLLMBackend(
            eng, tokenizer=ByteTokenizer(), partial_hold_s=0.5,
        )
        try:
            nodes = make_nodes()
            tail = self._items(backend, 3, nodes, age_s=age_s)
            backend._current_group = tail[0].group_key
            waves = deque((object(), []) for _ in range(in_flight))
            rest = backend._submit_waves(list(tail), waves, [])
            if ships:
                assert rest == [] and [n for _, n in eng.submits] == [3]
            else:
                assert rest == tail and eng.submits == []
                # company arrives: the held rows lead the full wave, the
                # newest row is the new tail (FIFO)
                more = self._items(backend, 2, nodes, first=10)
                rest = backend._submit_waves(rest + more, waves, [])
                assert [n for _, n in eng.submits] == [4]
                assert waves[-1][1] == tail + more[:1] and rest == more[1:]
        finally:
            backend.close()

    def test_tail_ships_when_a_group_switch_is_due(self):
        """Behind a deep pipeline the current group's tail holds while
        another group's items are still inside their fairness wait, and
        ships in the tick that switches: nothing more will batch with it."""
        from collections import deque

        eng = FakeEngine()
        backend = LocalLLMBackend(
            eng, tokenizer=ByteTokenizer(), group_switch_after_s=0.25,
        )
        try:
            tail = self._items(backend, 3, make_nodes(3), age_s=5.0)
            other = self._items(backend, 2, make_nodes(4), first=10)
            assert other[0].group_key != tail[0].group_key
            backend._current_group = tail[0].group_key
            waves = deque((object(), []) for _ in range(3))
            rest = backend._submit_waves(tail + other, waves, [])
            assert rest == tail + other and eng.submits == []
            for item in other:
                item.enqueued_at -= 0.3  # fairness wait over
            rest = backend._submit_waves(rest, waves, [])
            # the old group's three rows shipped, the engine switched, and
            # the new group's tail holds behind the (now four) waves
            assert [n for _, n in eng.submits] == [3]
            assert backend._current_group == other[0].group_key
            assert rest == other
        finally:
            backend.close()


    def test_tail_goes_with_the_engine_to_another_snapshot_of_its_cluster(self):
        """The same ready nodes under other metrics (the next snapshot of
        one cluster): the tail the engine would leave behind ragged joins
        the group it switches to, at its head, and ships with it. Another
        cluster's group (other nodes) never takes it: the test above."""
        import dataclasses
        from collections import deque

        eng = FakeEngine()
        backend = LocalLLMBackend(
            eng, tokenizer=ByteTokenizer(), group_switch_after_s=0.25,
        )
        try:
            nodes = make_nodes(3)
            drifted = [dataclasses.replace(n, cpu_usage_percent=n.cpu_usage_percent + 7.0)
                       for n in nodes]
            width = eng.max_slots
            full = self._items(backend, width, nodes, age_s=5.0)
            tail = self._items(backend, 3, nodes, first=20, age_s=5.0)
            other = self._items(backend, width - 3, drifted, first=10, age_s=0.3)
            assert other[0].group_key != tail[0].group_key
            assert other[0].group_key[1] == tail[0].group_key[1]  # the same ready nodes
            suffixes = [list(i.suffix_ids) for i in tail]
            backend._current_group = tail[0].group_key
            waves = deque((object(), []) for _ in range(3))
            rest = backend._submit_waves(full + tail + other, waves, [])
            # the old group's full wave shipped; its three stragglers and the
            # new group's rows make one full wave under the new prefix
            assert [n for _, n in eng.submits] == [width, width] and rest == []
            assert waves[-1][1] == tail + other
            assert {i.group_key for i in tail} == {other[0].group_key}
            assert all(i.prefix_ids == other[0].prefix_ids for i in tail)
            assert [list(i.suffix_ids) for i in tail] == suffixes  # the pod's part is untouched
            assert backend._current_group == other[0].group_key
        finally:
            backend.close()

    def test_tail_never_goes_back_to_an_earlier_snapshot(self):
        """A straggler of a snapshot that reached the backend BEFORE the
        current one (its first pod was seen earlier) takes no tail with it:
        a pod is never decided on older metrics than it was encoded under."""
        import dataclasses
        from collections import deque

        eng = FakeEngine()
        backend = LocalLLMBackend(
            eng, tokenizer=ByteTokenizer(), group_switch_after_s=0.25,
        )
        try:
            nodes = make_nodes(3)
            drifted = [dataclasses.replace(n, cpu_usage_percent=n.cpu_usage_percent + 7.0)
                       for n in nodes]
            width = eng.max_slots
            # the drifted snapshot is the EARLIER one: its first pod came first
            early = self._items(backend, 1, drifted, first=30, age_s=9.0)
            backend._submit_waves(early, deque(), [])
            full = self._items(backend, width, nodes, age_s=5.0)
            tail = self._items(backend, 3, nodes, first=20, age_s=5.0)
            straggler = self._items(backend, 1, drifted, first=10, age_s=0.3)
            assert straggler[0].group_key == early[0].group_key
            assert straggler[0].group_key[1] == tail[0].group_key[1]  # the same ready nodes
            key = tail[0].group_key
            backend._current_group = key
            eng.submits.clear()
            waves = deque((object(), []) for _ in range(3))
            backend._submit_waves(full + tail + straggler, waves, [])
            assert {i.group_key for i in tail} == {key}  # stayed with their snapshot
            assert [n for _, n in eng.submits][:2] == [width, 3]  # and shipped ragged under it
        finally:
            backend.close()


class TestFinishedWaveGoesFirst:
    """A wave that has finished and is not harvested yet goes before any
    further dispatch: a dispatch blocks while the device's queue is full,
    on the one thread that harvests (_submit_waves `owed`). Driven through
    _submit_waves with the in-flight deque planted, as above."""

    _items = staticmethod(TestTailHoldPolicy._items)

    @pytest.mark.parametrize(
        "oldest_ready,n_pending,submitted",
        [
            (True, 4, []),       # a full wave waits for the harvest
            (True, 9, []),       # so do two and a tail
            (False, 4, [4]),     # nothing finished: dispatched as before
            (False, 9, [4, 4]),  # (the tail of one holds behind two waves)
        ],
    )
    def test_no_dispatch_while_a_finished_wave_waits(
        self, oldest_ready, n_pending, submitted
    ):
        from collections import deque

        eng = FakeEngine()
        backend = LocalLLMBackend(eng, tokenizer=ByteTokenizer())
        try:
            nodes = make_nodes()
            items = self._items(backend, n_pending, nodes)
            backend._current_group = items[0].group_key
            oldest = FakeHandle(ready_at=0.0 if oldest_ready else float("inf"))
            waves = deque([(oldest, []), (FakeHandle(float("inf")), [])])
            rest = backend._submit_waves(list(items), waves, [])
            assert [n for _, n in eng.submits] == submitted
            assert rest == items[sum(submitted):]  # FIFO, nothing lost
        finally:
            backend.close()

    def test_finishing_mid_tick_stops_the_tick_s_dispatches(self):
        """The first dispatch returns when the wave on the device has
        finished (that is what it blocked on): the second full wave of the
        same tick stays pending, behind the harvest."""
        from collections import deque

        eng = FakeEngine()
        backend = LocalLLMBackend(eng, tokenizer=ByteTokenizer())
        try:
            nodes = make_nodes()
            items = self._items(backend, 2 * eng.max_slots, nodes)
            backend._current_group = items[0].group_key
            oldest = FakeHandle(ready_at=float("inf"))
            inner = eng.submit_wave

            def blocking_submit(prompts, max_new_tokens):
                oldest.ready_at = 0.0  # the queue had room again: a wave ended
                return inner(prompts, max_new_tokens)

            eng.submit_wave = blocking_submit
            waves = deque([(oldest, [])])
            rest = backend._submit_waves(list(items), waves, [])
            assert [n for _, n in eng.submits] == [eng.max_slots]
            assert rest == items[eng.max_slots:]
        finally:
            backend.close()

    @pytest.mark.parametrize("oldest_ready", [True, False])
    def test_group_switch_waits_for_the_harvest_too(self, oldest_ready):
        """A switch dispatches a prefix prefill; with a finished wave
        waiting it is put off a tick, the old group's tail is not cut off
        for it, and nothing changes group."""
        import dataclasses
        from collections import deque

        eng = FakeEngine()
        backend = LocalLLMBackend(
            eng, tokenizer=ByteTokenizer(), group_switch_after_s=0.25,
        )
        try:
            nodes = make_nodes(3)
            drifted = [dataclasses.replace(n, cpu_usage_percent=n.cpu_usage_percent + 7.0)
                       for n in nodes]
            tail = self._items(backend, 3, nodes, first=20, age_s=5.0)
            other = self._items(backend, 1, drifted, first=10, age_s=0.3)
            key = tail[0].group_key
            backend._current_group = key
            oldest = FakeHandle(ready_at=0.0 if oldest_ready else float("inf"))
            waves = deque([(oldest, [])] + [(FakeHandle(float("inf")), []) for _ in range(2)])
            rest = backend._submit_waves(tail + other, waves, [])
            if oldest_ready:
                assert eng.submits == [] and eng.prefixes == 0
                assert rest == tail + other
                assert {i.group_key for i in tail} == {key}
                assert backend._current_group == key
            else:
                assert eng.prefixes == 1 and rest == []
                assert [n for _, n in eng.submits] == [4]
                assert backend._current_group == other[0].group_key
        finally:
            backend.close()


class TestPoolRoleAndBatch:
    def test_decode_role_refuses_admission(self):
        from k8s_llm_scheduler_tpu.engine.backend import BackendError

        eng = FakeEngine(wave_s=0.05)
        backend = LocalLLMBackend(
            eng, tokenizer=ByteTokenizer(), pool_role="decode",
        )
        try:
            import pytest

            with pytest.raises(BackendError, match="refuses admission"):
                backend.get_scheduling_decision(make_pod(0), make_nodes())
            assert backend.role_refusals == 1
            # continuation (decode) work is served normally
            d = backend.get_scheduling_decision(
                make_pod(0), make_nodes(), work="decode"
            )
            assert d.selected_node == "node-1"
            assert backend.get_stats()["pool_role"] == "decode"
        finally:
            backend.close()

    def test_prepacked_batch_coalesces_and_isolates_failures(self):
        """get_scheduling_decisions_batch enqueues the WHOLE pack before
        waiting (the engine sees it together and coalesces it into full
        waves), returns outcomes positionally, and an infeasible pod
        fails alone."""
        import dataclasses

        from k8s_llm_scheduler_tpu.engine.backend import NoFeasibleNodeError

        eng = FakeEngine(wave_s=0.05)
        backend = LocalLLMBackend(
            eng, tokenizer=ByteTokenizer(), admit_wait_s=0.01,
        )
        try:
            nodes = make_nodes()
            pods = [make_pod(i) for i in range(4)]
            pods[2] = dataclasses.replace(
                pods[2], node_selector={"no": "where"}
            )
            out = backend.get_scheduling_decisions_batch(pods, nodes)
            assert len(out) == 4
            assert out[0].selected_node == "node-1"
            assert out[1].selected_node == "node-1"
            assert isinstance(out[2], NoFeasibleNodeError)
            assert out[3].selected_node == "node-1"
            # the 3 feasible pods rode at most one full wave each at the
            # stub's 4 slots — enqueue-before-wait means they were NOT
            # serialized into one wave per pod
            assert len(eng.submits) <= 2
            assert sum(n for _t, n in eng.submits) == 3
        finally:
            backend.close()
