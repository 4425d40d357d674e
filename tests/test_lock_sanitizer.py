"""Runtime lock-order sanitizer (k8s_llm_scheduler_tpu/testing.py).

The sanitizer is the runtime twin of graftlint's concurrency rules: it
wraps threading.Lock creation, records the cross-thread acquisition-order
graph, and flags (a) order cycles — latent ABBA deadlocks that a given
run only hits under exact interleaving — and (b) threading locks held
across an event-loop hop (the runtime shape of lock-across-await).

Also here, as the harness's other self-test: the per-test time limit of
tests/conftest.py (TEST_LIMIT_S, time_limit).
"""

import asyncio
import queue
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import conftest
import pytest

from k8s_llm_scheduler_tpu.testing import (
    LockOrderSanitizer,
    LockOrderViolation,
    async_deadline,
)


class TestCycleDetection:
    def test_seeded_abba_cycle_is_caught(self):
        """The canonical seeded deadlock: worker 1 takes A then B, worker 2
        takes B then A. Run sequentially the program completes fine — the
        deadlock only fires if both interleave between their first and
        second acquire — but the ORDER GRAPH has the A->B->A cycle either
        way, which is exactly what makes the hazard catchable
        deterministically."""
        san = LockOrderSanitizer()
        with san:
            lock_a = threading.Lock()
            lock_b = threading.Lock()

            def worker_ab():
                with lock_a:
                    with lock_b:
                        pass

            def worker_ba():
                with lock_b:
                    with lock_a:
                        pass

            t1 = threading.Thread(target=worker_ab)
            t1.start()
            t1.join()
            t2 = threading.Thread(target=worker_ba)
            t2.start()
            t2.join()
        assert san.violations, "ABBA cycle not detected"
        assert "cycle" in san.violations[0]
        with pytest.raises(LockOrderViolation):
            san.assert_clean()

    def test_consistent_order_is_clean(self):
        san = LockOrderSanitizer()
        with san:
            lock_a = threading.Lock()
            lock_b = threading.Lock()
            for _ in range(3):
                with lock_a:
                    with lock_b:
                        pass
        san.assert_clean()

    def test_three_lock_cycle(self):
        """Cycles longer than 2 (A->B->C->A) are found via the path walk,
        not just direct back-edges."""
        san = LockOrderSanitizer()
        with san:
            # distinct creation lines: site identity is file:line
            a = threading.Lock()
            b = threading.Lock()
            c = threading.Lock()
            with a:
                with b:
                    pass
            with b:
                with c:
                    pass
            with c:
                with a:
                    pass
        assert any("cycle" in v for v in san.violations)

    def test_same_site_locks_do_not_self_cycle(self):
        """Two locks from the SAME creation site (e.g. two instances of a
        class each holding self._lock) acquired nested must not report a
        one-node cycle — per-site identity collapses them."""
        san = LockOrderSanitizer()
        with san:
            def make():
                return threading.Lock()  # one site for both

            outer, inner = make(), make()
            with outer:
                with inner:
                    pass
        san.assert_clean()


class TestEventLoopHop:
    def test_lock_held_across_await_is_caught(self):
        san = LockOrderSanitizer()
        with san:
            lock = threading.Lock()

            async def bad():
                lock.acquire()  # graftlint: ok[lock-acquire-in-async] — deliberate hazard: this test exists to prove the runtime sanitizer catches it
                try:
                    # the loop runs the sleep timer callback -> a hop
                    await asyncio.sleep(0.01)
                finally:
                    lock.release()

            asyncio.run(bad())
        assert any("event-loop hop" in v for v in san.violations)

    def test_straight_line_critical_section_on_loop_is_clean(self):
        """The repo's sanctioned pattern — a brief `with lock:` with no
        awaits inside a coroutine — must not be flagged."""
        san = LockOrderSanitizer()
        with san:
            lock = threading.Lock()

            async def good():
                with lock:
                    x = sum(range(10))
                await asyncio.sleep(0)
                return x

            asyncio.run(good())
        san.assert_clean()

    def test_thread_side_hold_is_clean(self):
        """Locks held on plain worker threads (no loop) never produce hop
        reports regardless of how long the loop runs elsewhere."""
        san = LockOrderSanitizer()
        with san:
            lock = threading.Lock()
            done = threading.Event()

            def worker():
                with lock:
                    time.sleep(0.02)
                done.set()

            t = threading.Thread(target=worker)
            t.start()

            async def spin():
                async with async_deadline(5):
                    while not done.is_set():
                        await asyncio.sleep(0.002)

            asyncio.run(spin())
            t.join()
        san.assert_clean()


class TestHandOffAndNesting:
    def test_cross_thread_handoff_leaves_no_phantom_edges(self):
        """A lock acquired on one thread and released on another must not
        linger on the acquirer's held stack: the phantom entry would
        record edges from a lock nobody holds and manufacture a false
        cycle against the worker's own (legitimate) ordering."""
        san = LockOrderSanitizer()
        with san:
            lock_l = threading.Lock()
            lock_a = threading.Lock()

            lock_l.acquire()  # main thread acquires...
            t = threading.Thread(target=lock_l.release)  # ...worker releases
            t.start()
            t.join()

            # main: if L's residue survived, this records phantom L->A
            with lock_a:
                pass

            def worker():  # real, harmless ordering: A then L
                with lock_a:
                    with lock_l:
                        pass

            t2 = threading.Thread(target=worker)
            t2.start()
            t2.join()
        san.assert_clean()

    def test_nested_sanitizers_both_detect(self):
        """Suite-wide autouse + explicit fixture stack two sanitizers; the
        inner factory wraps the outer's. Both must still attribute locks
        to their REAL creation sites (distinct), or edge recording
        silently collapses to nothing."""
        outer = LockOrderSanitizer()
        with outer:
            inner = LockOrderSanitizer()
            with inner:
                a = threading.Lock()
                b = threading.Lock()
                with a:
                    with b:
                        pass
                with b:
                    with a:
                        pass
            assert any("cycle" in v for v in inner.violations)
        assert any("cycle" in v for v in outer.violations)


class TestInstrumentationCompat:
    def test_queue_and_condition_still_work_wrapped(self):
        """queue.Queue builds Conditions over threading.Lock(); the wrapped
        lock must satisfy the Condition protocol end to end."""
        san = LockOrderSanitizer()
        with san:
            q: queue.Queue = queue.Queue(maxsize=4)
            results = []

            def producer():
                for i in range(8):
                    q.put(i)

            def consumer():
                for _ in range(8):
                    results.append(q.get(timeout=5))

            threads = [
                threading.Thread(target=producer),
                threading.Thread(target=consumer),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
        assert results == list(range(8))
        san.assert_clean()

    def test_uninstall_restores_factory(self):
        orig = threading.Lock
        san = LockOrderSanitizer()
        san.install()
        assert threading.Lock is not orig
        san.uninstall()
        assert threading.Lock is orig
        # post-uninstall locks are plain again
        lock = threading.Lock()
        assert not hasattr(lock, "site")

    def test_locks_predating_install_are_ignored(self):
        before = threading.Lock()
        san = LockOrderSanitizer()
        with san:
            with before:  # un-instrumented: no bookkeeping, no crash
                pass
            assert san.locks_created == 0
        san.assert_clean()


class TestFixture:
    def test_fixture_passes_clean_code(self, lock_sanitizer):
        lock = threading.Lock()
        with lock:
            pass
        assert lock_sanitizer.locks_created >= 1


class TestPerTestLimit:
    def test_sleeping_body_fails_by_name_and_the_outer_limit_returns(
        self, monkeypatch, capfd
    ):
        """time_limit() on its own, nested inside this test's limit."""
        monkeypatch.setattr(conftest, "TEST_LIMIT_S", 0.2)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError, match=r"tests/x\.py::test_hangs .* 0\.2 s"):
            with conftest.time_limit("tests/x.py::test_hangs"):
                time.sleep(30)
        assert time.monotonic() - t0 < 5
        # every thread's stack went to stderr before the error was raised
        assert "most recent call first" in capfd.readouterr().err
        # this test's own limit, armed by the hook, is running again:
        # less than the 120 s it began with, repeating once fired
        left, interval = signal.getitimer(signal.ITIMER_REAL)
        assert 0 < left < 120.0 and interval == 1.0
        assert signal.getsignal(signal.SIGALRM) is not signal.SIG_DFL

    def test_swallowed_error_comes_again(self, monkeypatch):
        """Code under test that catches the TimeoutError does not get to
        hang after it: the alarm repeats until the block is left."""
        monkeypatch.setattr(conftest, "TEST_LIMIT_S", 0.2)
        with pytest.raises(TimeoutError):
            with conftest.time_limit("tests/x.py::test_swallows"):
                try:
                    time.sleep(30)
                except TimeoutError:
                    pass
                time.sleep(30)

    def test_hook_fails_the_hanging_test_and_the_next_one_runs(self, tmp_path):
        """The wiring, in a pytest of its own: with the constant cut to
        half a second a sleeping test fails with the TimeoutError that
        names it, and the test after it on the same worker passes — the
        timer was disarmed, or its next alarm would land in pytest."""
        (tmp_path / "test_limit_probe.py").write_text(
            "import time\n"
            "def test_hangs():\n    time.sleep(30)\n"
            "def test_next_runs():\n    time.sleep(0.1)\n"
        )
        proc = subprocess.run(
            [
                sys.executable, "-c",
                "import sys, pytest, conftest\n"
                "conftest.TEST_LIMIT_S = 0.5\n"
                "sys.exit(pytest.main(sys.argv[1:]))",
                str(tmp_path), "-q", "-p", "conftest", "-p", "no:cacheprovider",
                "-p", "no:xdist", "-p", "no:randomly",
            ],
            cwd=Path(conftest.__file__).parent,
            capture_output=True, text=True, timeout=100,
        )
        out = proc.stdout + proc.stderr
        assert proc.returncode == 1, out
        assert "1 failed, 1 passed" in out, out
        assert (
            "TimeoutError: test_limit_probe.py::test_hangs still running "
            "after 0.5 s" in out
        ), out
