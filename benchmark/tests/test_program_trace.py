"""The reduction behind the per-program and per-thread metrics
(metrics/_program_trace.py) reproduces known numbers from a small recorded
trace (tests/data/scoped_trace.xplane.pb, taken on a TPU v5 lite by
tests/record_scoped_fixture.py: three runs of a program named `wave` with a
`suffix_prefill` layer scan and a `block_decode` loop of five iterations,
one run of `prefix_prefill_kv`, `engine.*` spans with a `wave` stat on a
worker thread, `sched.*` spans on the main thread).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_program_trace.py -q
"""

from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def profile():
    from harness import xplane

    return xplane.load(str(DATA / "scoped_trace.xplane.pb"))


@pytest.fixture(scope="module")
def want():
    return json.loads((DATA / "scoped_trace.expected.json").read_text())


def context(profile, acks=8):
    return SimpleNamespace(profile=profile, outcome=SimpleNamespace(trace_span=(0.0, 1.0)),
                           cluster=SimpleNamespace(acks={f"pod-{i}": 0.1 * i for i in range(acks)}))


def test_module_runs_by_name_and_the_split_at_while(profile, want):
    from metrics import _program_trace as pt

    runs = pt.module_runs(profile)
    assert [name for _a, _b, name in runs].count("jit_wave") == 3
    assert [name for _a, _b, name in runs].count("jit_prefix_prefill_kv") == 1
    got = pt.reduced(context(profile))
    for key, value in want["benchmark"].items():
        assert got[key] == pytest.approx(value, rel=1e-9), key
    # the loop runs the four layers five times, the prefill once: the split
    # at `while` puts about five sixths of a wave run inside it
    assert got["wave_runs"] == 3 and got["prefix_runs"] == 1 and got["binds"] == 8
    assert 0.7 < got["decode_s"] / got["wave_s"] < 0.9
    # the device numbers telescope: the runs and what lies outside them are the busy time
    assert got["wave_s"] + got["prefix_s"] + got["other_s"] == pytest.approx(got["busy_s"], rel=0.03)
    assert got["aligned"] and got["submits"] == got["harvests"] == 3
    assert got["wave_numbers"] == [1, 2, 3]


def test_decode_loop_is_the_while_that_holds_another():
    from metrics import _program_trace as pt

    # suffix scan, then the decode loop holding two layer scans
    assert pt.decode_loop_ns([(0, 10), (20, 100), (25, 40), (50, 70)]) == 80
    # no nesting (an unrolled layer scan): the run's last outermost while
    assert pt.decode_loop_ns([(0, 10), (20, 100)]) == 80
    assert pt.decode_loop_ns([]) == 0.0


def test_self_time_of_a_thread(profile, want):
    from metrics import _program_trace as pt

    threads = pt.host_spans(profile, ("engine.",))
    worker = max(threads.values(), key=len)
    got = pt.self_times(worker)
    assert [[n, t] for n, t in got] == [[n, pytest.approx(t)] for n, t in want["worker_self_ns"]]
    by_name: dict[str, float] = {}
    for name, t in got:
        by_name[name] = by_name.get(name, 0.0) + t
    # three ticks, each: a submit that works 0.5 ms around its dispatch, a 2 ms
    # poll, a harvest that waits for the device and then works 1 ms, a 1 ms
    # resolve; 4 ms on the queue between ticks
    # (a sleep is at least what was asked for, and on a shared host up to twice that)
    for name, asked_ns in (("engine.queue_wait", 4e6), ("engine.submit_wave", 0.5e6),
                           ("engine.harvest_poll", 2e6), ("engine.harvest_wave", 1e6),
                           ("engine.resolve", 1e6)):
        assert 3 * asked_ns <= by_name[name] <= 3 * 2.5 * asked_ns, name
    assert by_name["engine.tick"] < 1e6  # all of a tick is inside its spans
    # a span's own stats come through, the one set at its end too
    assert [[r[2], {k: int(r[3][k]) for k in ("wave", "rows", "bucket", "model_calls") if k in r[3]}]
            for r in worker] == want["worker_stats"]
    harvests = [r[3] for r in worker if r[2] == "engine.harvest_wave"]
    assert [{k: int(v) for k, v in h.items()} for h in harvests] == [
        {"wave": k, "rows": 8, "bucket": 128, "model_calls": 5} for k in (1, 2, 3)]
    # worker self time leaves out the five that wait
    r = pt.reduced(context(profile))
    work = sum(t for n, t in got if n not in pt.WORKER_WAITS)
    assert r["worker_self_s"] == pytest.approx(work * 1e-9)
    assert r["loop_self_s"] == pytest.approx(want["benchmark"]["loop_self_s"])
    assert r["loop_spans"] == 7  # render, delta_encode, tokenize twice; bind_call once


def test_a_program_that_names_nothing_reads_none(profile):
    """The parent of PR 25: no `jit_wave` run, no `engine.*` span. Every
    reader returns None and none raises."""
    from harness import xplane
    from metrics import _program_trace as pt

    import run as bench_run

    plain = xplane.load(str(DATA / "small_trace.xplane.pb"))
    ctx = context(plain)
    assert pt.reduced(ctx) is None
    names = ["wave_device_ms.tput", "decode_device_ms_per_bind.tput", "suffix_prefill_device_ms_per_bind.tput",
             "prefix_prefill_device_ms_per_bind.tput", "other_device_ms_per_bind.tput",
             "worker_self_ms_per_wave.tput", "loop_self_ms_per_bind.tput"]
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    assert set(names) <= {m["name"] for m in bench["per_layer"]}
    assert all(bench_run.reader_for(n)(ctx) is None for n in names)
    # and on the named trace each is a number; the four per-bind device numbers sum to busy per bind
    ctx = context(profile)
    values = {n: bench_run.reader_for(n)(ctx) for n in names}
    assert all(isinstance(v, float) and v >= 0.0 for v in values.values()), values
    r = pt.reduced(ctx)
    assert sum(values[n] for n in names[1:5]) == pytest.approx(1000.0 * r["busy_s"] / r["binds"], rel=0.03)
    assert values["wave_device_ms.tput"] == pytest.approx(1000.0 * r["wave_s"] / 3)
