"""Fixture generators shared by tests and bench.py.

The reference ships 3 nginx fixture pods with graduated requests
(reference ai-test-pods.yaml:1-44: 100m/128Mi, 250m/256Mi, 500m/512Mi)
targeting schedulerName ai-llama-scheduler. `fixture_pods()` reproduces that
workload; `synthetic_cluster`/`pod_burst` generate the BASELINE stress shapes
(64/256-node clusters, 1000-pod bursts).

Also home of `async_deadline()` — the Python-3.10-compatible stand-in for
the 3.11+ ``asyncio.timeout`` context manager that every async test's
watchdog goes through (the package floor is >=3.10; tools/graftlint's
py310 rule family keeps direct 3.11+-only calls from creeping back in) —
and of `LockOrderSanitizer`, the runtime half of the concurrency
discipline graftlint checks statically: it wraps `threading.Lock`
creation for a test's duration, records the cross-thread lock
ACQUISITION-ORDER graph, and flags order cycles (latent ABBA deadlocks
that a run only hits under exact interleaving) and locks held across an
event-loop hop (the loop ran other callbacks while a threading lock was
held). Opt in per test via the `lock_sanitizer` fixture (tests/conftest),
or across the whole fast tier with GRAFT_LOCK_SANITIZER=1.
"""

from __future__ import annotations

import asyncio
import threading
from pathlib import Path

from k8s_llm_scheduler_tpu.cluster.fake import FakeCluster, FakeNode
from k8s_llm_scheduler_tpu.cluster.interface import RawPod

SCHEDULER_NAME = "ai-llama-scheduler"
# The committed 4k-vocab BPE tokenizer (a HF tokenizer.json directory):
# what bench.py and chip_smoke.py serve with, no hub needed.
BPE_FIXTURE = str(Path(__file__).resolve().parent / "assets" / "bpe4k")


class _Py310Deadline:
    """Minimal backport of the 3.11 timeout context manager: arm a timer
    that cancels the CURRENT task; translate the resulting CancelledError
    into TimeoutError iff this deadline (not an outer cancel) fired."""

    def __init__(self, seconds: float) -> None:
        self._seconds = seconds
        self._fired = False
        self._handle = None
        self._task = None

    async def __aenter__(self) -> "_Py310Deadline":
        self._task = asyncio.current_task()
        loop = asyncio.get_running_loop()
        self._handle = loop.call_later(self._seconds, self._on_timeout)
        return self

    def _on_timeout(self) -> None:
        self._fired = True
        if self._task is not None:
            self._task.cancel()

    async def __aexit__(self, exc_type, exc, tb) -> bool:
        if self._handle is not None:
            self._handle.cancel()
        if self._fired:
            if exc_type is asyncio.CancelledError:
                raise TimeoutError(
                    f"deadline of {self._seconds}s expired"
                ) from exc
            if exc_type is None:
                # Timer fired in the gap between the block's last await and
                # exit: the task.cancel() is still pending and would escape
                # as a bare CancelledError at the caller's NEXT await.
                # Absorb it at a checkpoint here and report the expiry
                # (3.11's native timeout resolves this boundary the same
                # way, via Task.uncancel bookkeeping).
                try:
                    await asyncio.sleep(0)
                except asyncio.CancelledError:
                    raise TimeoutError(
                        f"deadline of {self._seconds}s expired"
                    ) from None
        return False


def async_deadline(seconds: float):
    """``async with async_deadline(30): ...`` — bound an async block's wall
    time. Python 3.11+'s native scoped timeout when available (it handles
    nested-cancellation bookkeeping via Task.uncancel); a call_later-based
    shim with the same raise-TimeoutError contract on 3.10."""
    native = getattr(asyncio, "timeout", None)  # 3.11+
    if native is not None:
        return native(seconds)
    return _Py310Deadline(seconds)


# ------------------------------------------------------------------------
# Runtime lock-order sanitizer
# ------------------------------------------------------------------------


class LockOrderViolation(AssertionError):
    """A lock-discipline violation observed at runtime (order cycle or a
    lock held across an event-loop hop)."""


class _SanitizedLock:
    """Drop-in wrapper around a real `_thread.lock` that reports acquire/
    release events to its sanitizer. Identity for the order graph is the
    CREATION SITE (file:line), not the instance — two instances of the
    same class's `self._lock` are one graph node, so an ABBA cycle between
    two objects of the same class is still a cycle."""

    __slots__ = ("_real", "_san", "site", "_holder")

    def __init__(self, real, sanitizer: "LockOrderSanitizer", site: str) -> None:
        self._real = real
        self._san = sanitizer
        self.site = site
        # ident of the thread currently holding this lock (None when free
        # or released cross-thread) — lets the sanitizer purge hand-off
        # residue from the acquirer's held stack (see _note_acquire)
        self._holder: int | None = None

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self._real.acquire(blocking, timeout)
        if got:
            self._holder = threading.get_ident()
            self._san._note_acquire(self)
        return got

    def release(self) -> None:
        self._san._note_release(self)
        self._holder = None
        self._real.release()

    def locked(self) -> bool:
        return self._real.locked()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()

    def _at_fork_reinit(self) -> None:  # threading internals call this
        self._real._at_fork_reinit()

    def __repr__(self) -> str:
        return f"<SanitizedLock {self.site} {self._real!r}>"


class LockOrderSanitizer:
    """Wrap `threading.Lock` creation; record the acquisition graph; fail
    on cycles and on locks held across an event-loop hop.

    Checks (both are the runtime twin of a graftlint rule):

    - **order cycle**: edge A->B is recorded when a thread acquires B
      while holding A. A cycle in that graph is a latent deadlock even if
      this run's interleaving never wedged — exactly the class the test
      suite can only catch probabilistically.
    - **event-loop hop**: acquiring a threading lock on a loop thread and
      holding it across a loop iteration (detected via a patched
      `asyncio.events.Handle._run` tick counter: if the loop ran any
      OTHER callback between acquire and release, the holder suspended
      mid-critical-section — the runtime shape of `lock-across-await`).

    Violations are recorded, not raised at the fault site (raising inside
    arbitrary third-party acquire paths corrupts the code under test);
    `assert_clean()` — which the pytest fixture calls at teardown —
    raises LockOrderViolation with every observation.

    Scope: only locks CREATED while installed are tracked (the fixture
    installs before the test body, so objects the test builds are
    covered); `threading.RLock` is left alone (logging and interpreter
    internals). Use as a context manager, or install()/uninstall()."""

    def __init__(self) -> None:
        self._orig_lock = None
        self._orig_handle_run = None
        self._meta = threading.Lock()  # guards graph/violations (real lock)
        self._tls = threading.local()
        self.edges: dict[str, set[str]] = {}
        self.edge_where: dict[tuple[str, str], str] = {}
        self.violations: list[str] = []
        self.locks_created = 0

    # ------------------------------------------------------------ lifecycle
    def install(self) -> "LockOrderSanitizer":
        if self._orig_lock is not None:
            raise RuntimeError("sanitizer already installed")
        self._orig_lock = threading.Lock
        sanitizer = self

        def make_lock():
            sanitizer.locks_created += 1
            return _SanitizedLock(
                sanitizer._orig_lock(), sanitizer, sanitizer._creation_site()
            )

        threading.Lock = make_lock  # type: ignore[assignment]

        # Event-loop tick counter: every callback the loop runs bumps the
        # per-thread counter, so "held across a hop" is a counter delta.
        handle_cls = asyncio.events.Handle
        self._orig_handle_run = handle_cls._run
        orig_run = self._orig_handle_run

        def counting_run(handle_self):
            tls = sanitizer._tls
            tls.loop_ticks = getattr(tls, "loop_ticks", 0) + 1
            return orig_run(handle_self)

        handle_cls._run = counting_run  # type: ignore[assignment]
        return self

    def uninstall(self) -> None:
        if self._orig_lock is None:
            return
        threading.Lock = self._orig_lock  # type: ignore[assignment]
        asyncio.events.Handle._run = self._orig_handle_run  # type: ignore
        self._orig_lock = None
        self._orig_handle_run = None

    def __enter__(self) -> "LockOrderSanitizer":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    # ------------------------------------------------------------ recording
    @staticmethod
    def _creation_site() -> str:
        """file:line of the threading.Lock() caller — skipping any frames
        from THIS module, so stacked sanitizers (suite-wide autouse plus
        an explicit fixture: the inner factory calls the outer factory)
        still attribute every lock to its real creation site instead of
        collapsing all locks onto one make_lock line (which would zero
        out edge recording — edges require distinct sites)."""
        import sys

        frame = sys._getframe(1)
        while frame is not None and frame.f_code.co_filename == __file__:
            frame = frame.f_back
        if frame is None:  # pragma: no cover - interpreter-internal caller
            return "<unknown>:0"
        # last TWO path components: two files sharing a basename AND a
        # line number must not collapse into one graph node (a collision
        # could weld unrelated locks together and report a false cycle)
        tail = "/".join(frame.f_code.co_filename.rsplit("/", 2)[-2:])
        return f"{tail}:{frame.f_lineno}"

    def _held(self) -> list:
        stack = getattr(self._tls, "held", None)
        if stack is None:
            stack = self._tls.held = []
        return stack

    def _loop_running_here(self) -> bool:
        try:
            asyncio.get_running_loop()
            return True
        except RuntimeError:
            return False

    def _note_acquire(self, lock: _SanitizedLock) -> None:
        held = self._held()
        # Purge hand-off residue: a lock acquired HERE but released on
        # another thread keeps its stack entry (the releasing thread's
        # _note_release can't see this stack). Its _holder is by then
        # None or another thread — treating it as still-held would record
        # phantom edges and manufacture false cycles.
        me = threading.get_ident()
        if held:
            held[:] = [e for e in held if e[0]._holder == me]
        tick = (
            getattr(self._tls, "loop_ticks", 0)
            if self._loop_running_here() else None
        )
        new_edges = [
            (h.site, lock.site) for h, _t in held if h.site != lock.site
        ]
        held.append((lock, tick))
        if not new_edges:
            return
        with self._meta:
            for a, b in new_edges:
                if b in self.edges.setdefault(a, set()):
                    continue
                self.edges[a].add(b)
                self.edge_where[(a, b)] = threading.current_thread().name
                cycle = self._find_path(b, a)
                if cycle is not None:
                    self.violations.append(
                        "lock-order cycle: "
                        + " -> ".join([a] + cycle)
                        + f" (edge {a} -> {b} closed the cycle on thread "
                        f"{threading.current_thread().name}; a cross-thread "
                        f"interleaving of these acquisitions deadlocks)"
                    )

    def _find_path(self, src: str, dst: str) -> list[str] | None:
        """DFS path src -> ... -> dst in the edge graph (caller holds
        _meta). Returns the node list after src, or None."""
        seen = set()
        stack: list[tuple[str, list[str]]] = [(src, [src])]
        while stack:
            node, path = stack.pop()
            if node == dst:
                return path
            if node in seen:
                continue
            seen.add(node)
            for nxt in self.edges.get(node, ()):
                stack.append((nxt, path + [nxt]))
        return None

    def _note_release(self, lock: _SanitizedLock) -> None:
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i][0] is lock:
                _l, tick = held.pop(i)
                if tick is not None:
                    now = getattr(self._tls, "loop_ticks", 0)
                    if now != tick:
                        with self._meta:
                            self.violations.append(
                                f"lock {lock.site} was held across an "
                                f"event-loop hop ({now - tick} other "
                                f"callback(s) ran on the loop while it was "
                                f"held) — a threading lock in a coroutine "
                                f"must not span an await"
                            )
                return
        # release of a lock acquired before install (or on another
        # thread's stack for hand-off patterns): not ours to judge

    # ------------------------------------------------------------ reporting
    def assert_clean(self) -> None:
        if self.violations:
            raise LockOrderViolation(
                f"{len(self.violations)} lock-discipline violation(s):\n"
                + "\n".join(f"  - {v}" for v in self.violations)
            )


def fixture_pods(scheduler_name: str = SCHEDULER_NAME) -> list[RawPod]:
    """The reference's 3 graduated nginx test pods (ai-test-pods.yaml)."""
    shapes = [
        ("ai-test-pod-1", "100m", "128Mi"),
        ("ai-test-pod-2", "250m", "256Mi"),
        ("ai-test-pod-3", "500m", "512Mi"),
    ]
    return [
        RawPod(
            name=name,
            namespace="default",
            scheduler_name=scheduler_name,
            container_requests=({"cpu": cpu, "memory": mem},),
        )
        for name, cpu, mem in shapes
    ]


def synthetic_cluster(
    n_nodes: int = 3,
    cpu_cores: float = 16.0,
    memory_gb: float = 64.0,
    max_pods: int = 110,
    load_spread: bool = True,
) -> FakeCluster:
    """A FakeCluster with n nodes at varied synthetic load levels."""
    cluster = FakeCluster()
    for i in range(n_nodes):
        load = (i * 37 % 90) if load_spread else None
        cluster.add_node(
            FakeNode(
                name=f"node-{i}",
                cpu_capacity_cores=cpu_cores,
                memory_capacity_gb=memory_gb,
                max_pods=max_pods,
                cpu_usage_percent=float(load) if load is not None else None,
                memory_usage_percent=float(load) if load is not None else None,
                labels={"zone": f"z{i % 4}"},
            )
        )
    return cluster


def pod_burst(
    n_pods: int,
    scheduler_name: str = SCHEDULER_NAME,
    distinct_shapes: int = 8,
) -> list[RawPod]:
    """A burst of pending pods with `distinct_shapes` resource shapes.

    distinct_shapes controls the decision-cache hit rate: a 1000-pod burst
    with 8 shapes means ~992 decisions are cache-servable, which mirrors real
    bursts (replicas of few deployments) and the reference's cache-key
    equivalence design (scheduler.py:265-271).
    """
    pods = []
    for i in range(n_pods):
        shape = i % distinct_shapes
        cpu_m = 100 + 50 * shape
        mem_mi = 128 * (1 + shape % 4)
        pods.append(
            RawPod(
                name=f"burst-pod-{i}",
                namespace="default",
                scheduler_name=scheduler_name,
                container_requests=({"cpu": f"{cpu_m}m", "memory": f"{mem_mi}Mi"},),
                priority=shape % 3,
            )
        )
    return pods
