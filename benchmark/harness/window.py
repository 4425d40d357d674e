"""Drives `Scheduler.run()` with one traffic mix and measures the window.

One process, one event loop: the scheduler's watch loop, the generator and
the cluster's metric drift share it; the engine's worker thread is the
program's own. Pods reach the scheduler as watch events (FakeCluster.add_pod)
and a bind counts at the instant the cluster acknowledges it.
"""

from __future__ import annotations

import asyncio
import time

from harness import traffic as T
from harness.system import raw_pod


LATE_WAIT_S = 60.0


async def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        await asyncio.sleep(min(left, 0.25) if left > 0.01 else 0)


async def _drift(cluster, spec: dict, seed: int, t_first: float, t_end: float) -> None:
    period = spec["drift"]["period_s"]
    ticks = int((t_end - t_first) / period) + 2
    for i, updates in enumerate(T.drift_schedule(spec, seed, ticks)):
        due = t_first + i * period
        if due >= t_end:
            return
        await _sleep_until(due)
        cluster.set_usage(updates)


class Outcome:
    """What the window saw, before any metric is made of it."""

    def __init__(self) -> None:
        self.t0 = self.t1 = 0.0
        self.due: dict[str, float] = {}      # pod name -> due time (timetable)
        self.sent: dict[str, float] = {}     # pod name -> ADD event sent
        self.window_pods: list[str] = []     # pods due inside the window (timetable)
        self.before: dict = {}
        self.after: dict = {}
        self.trace_span: tuple[float, float] | None = None
        self.pool_exhausted = False          # closed loop: no pod was left to release


async def run_window(scheduler, cluster, mix: dict, seed: int, seconds: float,
                     snapshot_fn, tracer=None) -> Outcome:
    """Warm traffic, then the window of `seconds`. `snapshot_fn()` copies
    the program's counters; `tracer` (traced runs) is entered for a slice
    of the window."""
    out = Outcome()
    task = asyncio.create_task(scheduler.run())
    await asyncio.sleep(0.05)  # the watch is open before the first ADD
    spec = mix["cluster_spec"]
    stopping = False
    side: list[asyncio.Task] = []
    try:
        if mix["kind"] == "closed_depth":
            pods = T.closed_depth_pods(mix, seed)
            cursor = 0

            def release(_name=None) -> None:
                nonlocal cursor
                if stopping:
                    return
                if cursor >= len(pods):
                    out.pool_exhausted = True  # the rate would be the pool's, not the system's
                    return
                plan = pods[cursor]
                cursor += 1
                out.sent[plan.name] = time.perf_counter()
                cluster.add_pod(raw_pod(plan))

            cluster.on_ack = release
            t_start = time.perf_counter()
            side.append(asyncio.create_task(
                _drift(cluster, spec, seed, t_start, t_start + mix["warm_traffic_s"] + seconds + 30.0)))
            for _ in range(mix["depth"]):
                release()
            # the window opens at the first bind acknowledged after the warm
            # traffic: binds come a wave at a time, and a window that opened
            # at a random phase of that cycle would count one wave more or less
            await _sleep_until(t_start + mix["warm_traffic_s"])
            seen = cluster.bind_count
            while cluster.bind_count == seen and not out.pool_exhausted:
                await asyncio.sleep(0.001)
            out.t0 = max(cluster.acks.values())
            out.t1 = out.t0 + seconds
        else:
            warm, bursts = T.timetable(mix, seed, seconds)
            for burst in warm:
                for plan in burst.pods:
                    cluster.add_pod(raw_pod(plan))
                names = [p.name for p in burst.pods]
                while not all(n in cluster.acks for n in names):
                    await asyncio.sleep(0.005)
            out.t0 = time.perf_counter() + mix["warm_quiet_s"]
            out.t1 = out.t0 + seconds
            side.append(asyncio.create_task(
                _drift(cluster, spec, seed, out.t0 - mix["warm_quiet_s"], out.t1)))

            async def send() -> None:
                for burst in bursts:
                    due = out.t0 + burst.due_s
                    await _sleep_until(due)
                    for plan in burst.pods:
                        out.due[plan.name] = due
                        out.sent[plan.name] = time.perf_counter()
                        out.window_pods.append(plan.name)
                        cluster.add_pod(raw_pod(plan))

            side.append(asyncio.create_task(send()))

        await _sleep_until(out.t0)
        out.before = snapshot_fn()
        if tracer is not None:
            # Stopping the profiler blocks this loop while it writes, so the
            # traced slice ends where nothing is due: a closed loop's at the
            # window's close, a timetable's one period after its last burst,
            # and that one covers the last whole cycle of burst sizes.
            if mix["kind"] == "closed_depth":
                stop = out.t1
                start = max(stop - tracer.seconds, out.t0)
            else:
                cycle = len(mix["burst_sizes"])
                start = out.t0 + bursts[-cycle].due_s - 0.05
                stop = min(out.t0 + bursts[-1].due_s + mix["period_s"], out.t1)
            await _sleep_until(start)
            tracer.start()
            ta = time.perf_counter()
            try:
                await _sleep_until(stop)
                out.trace_span = (ta, time.perf_counter())
            finally:
                tracer.stop()
        await _sleep_until(out.t1)
        out.after = snapshot_fn()
        if out.pool_exhausted:
            raise RuntimeError(
                f"traffic {mix['name']!r}: all {mix['pool_blocks'] * T.BLOCK} pods of the pool were released "
                "before the window closed; the cell needs a traffic file with more pool_blocks")
        if mix["kind"] == "timetable":
            # an answer that comes late is late, not wrong: wait for every
            # pod that was due in the window, up to a minute past the close
            give_up = out.t1 + LATE_WAIT_S
            while time.perf_counter() < give_up and not all(p in cluster.acks for p in out.window_pods):
                await asyncio.sleep(0.01)
    finally:
        stopping = True
        cluster.on_ack = None
        for t in side:
            t.cancel()
        for t in side:
            try:
                await t
            except asyncio.CancelledError:
                pass
        scheduler.stop()
        cluster.close()
        await asyncio.wait_for(task, timeout=120)
    return out
