"""Test harness config: run JAX on a virtual 8-device CPU mesh.

Must run before any `import jax` (pytest imports conftest first), so the
multi-chip sharding paths are exercised hermetically without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force CPU
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# force CPU through the config API too: a jax imported before this file
# ran captured the environment as it was then (must happen before the
# first backend use).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import asyncio  # noqa: E402
import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import inspect  # noqa: E402
import signal  # noqa: E402

import pytest  # noqa: E402

from k8s_llm_scheduler_tpu.types import NodeMetrics, PodSpec  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: run test as a coroutine")


@pytest.fixture
def lock_sanitizer():
    """Opt-in runtime lock-order sanitizer (k8s_llm_scheduler_tpu/testing
    LockOrderSanitizer): wraps threading.Lock creation for the test body,
    fails the test at teardown on acquisition-order cycles or locks held
    across an event-loop hop."""
    from k8s_llm_scheduler_tpu.testing import LockOrderSanitizer

    san = LockOrderSanitizer()
    with san:
        yield san
    san.assert_clean()


# GRAFT_LOCK_SANITIZER=1 arms the sanitizer for EVERY test — the "record
# the acquisition graph across the fast tier" sweep mode. Off by default:
# wrapping threading.Lock globally taxes every queue/condition op.
_SANITIZE_ALL = os.environ.get("GRAFT_LOCK_SANITIZER") == "1"


@pytest.fixture(autouse=_SANITIZE_ALL)
def _lock_sanitizer_everywhere(request):
    # The sanitizer's own suite seeds deliberate violations (ABBA cycles,
    # held-across-hop) and asserts on factory install/uninstall state —
    # an ambient sanitizer would both catch the seeded hazards and break
    # the factory assertions, so its module opts out of the sweep.
    if not _SANITIZE_ALL or request.module.__name__ == "test_lock_sanitizer":
        yield
        return
    from k8s_llm_scheduler_tpu.testing import LockOrderSanitizer

    san = LockOrderSanitizer()
    with san:
        yield
    san.assert_clean()


# Seconds a test's call phase may take. pytest-timeout is not in the image,
# and without a limit of its own ONE hanging test holds its xdist worker
# until the whole run's clock cuts it (tier 1, until PR 26). With it a hang
# costs that worker two minutes and one failure that names the test and
# shows every thread's stack. One constant: no marker or variable moves it.
TEST_LIMIT_S = 120.0


@contextlib.contextmanager
def time_limit(nodeid: str):
    """Fail the enclosed block with a TimeoutError naming `nodeid` once it
    has run TEST_LIMIT_S: SIGALRM, so main thread only — where pytest and
    every xdist worker run test bodies. All threads' stacks go to stderr
    first. After it first fires the alarm repeats each second: code under
    test that swallows the error (`except Exception` around one pod,
    `except TimeoutError` around a wait_for) meets it again. The previous
    timer and handler come back on exit, so blocks nest."""
    limit = TEST_LIMIT_S
    fired = False

    def expired(signum, frame):
        nonlocal fired
        if not fired:
            fired = True
            # __stderr__: capsys swaps sys.stderr for an object with no
            # fileno; fd 2 still lands in pytest's captured stderr
            faulthandler.dump_traceback(file=sys.__stderr__)
        raise TimeoutError(
            f"{nodeid} still running after {limit:g} s "
            f"(TEST_LIMIT_S, tests/conftest.py)"
        )

    old_handler = signal.signal(signal.SIGALRM, expired)
    old_timer = signal.setitimer(signal.ITIMER_REAL, limit, 1.0)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *old_timer)
        signal.signal(signal.SIGALRM, old_handler)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with time_limit(item.nodeid):
        return (yield)


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Minimal async test support (pytest-asyncio is not in the image)."""
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(func(**kwargs))
        return True
    return None


def make_node(
    name: str = "node-1",
    cpu_pct: float = 30.0,
    mem_pct: float = 40.0,
    cpu_cores: float = 8.0,
    mem_gb: float = 32.0,
    pods: int = 10,
    max_pods: int = 110,
    ready: bool = True,
    labels: dict | None = None,
    taints: tuple = (),
) -> NodeMetrics:
    return NodeMetrics(
        name=name,
        cpu_usage_percent=cpu_pct,
        memory_usage_percent=mem_pct,
        available_cpu_cores=cpu_cores,
        available_memory_gb=mem_gb,
        pod_count=pods,
        max_pods=max_pods,
        labels=labels or {},
        taints=taints,
        conditions={"Ready": "True" if ready else "False"},
    )


def make_pod(
    name: str = "pod-1",
    namespace: str = "default",
    cpu: float = 0.1,
    mem_gb: float = 0.125,
    priority: int = 0,
    node_selector: dict | None = None,
    tolerations: tuple = (),
) -> PodSpec:
    return PodSpec(
        name=name,
        namespace=namespace,
        cpu_request=cpu,
        memory_request=mem_gb,
        node_selector=node_selector or {},
        tolerations=tolerations,
        priority=priority,
    )


@pytest.fixture
def three_nodes():
    """A 3-node cluster like the reference's Minikube setup (README.md:70)."""
    return [
        make_node("node-a", cpu_pct=20.0, mem_pct=30.0, pods=5),
        make_node("node-b", cpu_pct=60.0, mem_pct=50.0, pods=20),
        make_node("node-c", cpu_pct=90.0, mem_pct=85.0, pods=60),
    ]
