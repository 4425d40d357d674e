"""The scope level of the trace as metrics (harness/scopes.py,
metrics/_scope_trace.py and the six readers on it) reproduces, from the
recorded trace tests/data/scoped_trace.xplane.pb, the seconds that the
program's own reduction (`tools/trace_scopes.py`) found in the same file
and that tests/record_scoped_fixture.py wrote beside it
(scoped_trace.expected.json, `program`); and run.py keeps the capture on
disk for exactly as long as the readers run.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_scope_trace.py -q
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))
DATA = Path(__file__).resolve().parent / "data"
SCOPED = DATA / "scoped_trace.xplane.pb"
BINDS = 8
NAMES = ["mlp_device_ms_per_bind.tput", "attn_device_ms_per_bind.tput",
         "kv_writeback_device_ms_per_bind.tput", "lm_head_device_ms_per_bind.tput",
         "layer_loop_device_ms_per_bind.tput", "unscoped_device_share.tput"]


def context(path):
    return SimpleNamespace(xplane_path=None if path is None else str(path),
                           outcome=SimpleNamespace(trace_span=(0.0, 1.0)),
                           cluster=SimpleNamespace(acks={f"pod-{i}": 0.1 * i for i in range(BINDS)}))


@pytest.fixture(scope="module")
def want():
    return json.loads((DATA / "scoped_trace.expected.json").read_text())["program"]


def test_seconds_by_scope_are_the_programs_own_reduction(want):
    from metrics import _scope_trace as st

    got = st.reduced(context(SCOPED))
    summed: dict[str, float] = {}
    for per in want["scopes"].values():
        for scope, seconds in per.items():
            summed[scope] = summed.get(scope, 0.0) + seconds
    assert got["scopes"] == pytest.approx(summed, rel=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["binds"] == BINDS
    # every operation is counted once: the scopes, with what has none, are the busy time
    assert sum(got["scopes"].values()) == pytest.approx(got["busy_s"], rel=1e-9)


def test_the_six_readers_on_the_recorded_trace(want):
    import run as bench_run

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(NAMES) <= set(entries)
    assert all(entries[n]["source"] == "device_trace" and entries[n]["moves"] == "binds_per_s" for n in NAMES)
    ctx = context(SCOPED)
    got = {n: bench_run.reader_for(n)(ctx) for n in NAMES}
    wave, prefix = want["scopes"]["wave"], want["scopes"]["prefix_prefill_kv"]
    mlp = wave["block_decode/model/mlp"] + wave["suffix_prefill/mlp"] + prefix["prefix_prefill/mlp"]
    assert got["mlp_device_ms_per_bind.tput"] == pytest.approx(1000.0 * mlp / BINDS, rel=1e-9)
    assert got["layer_loop_device_ms_per_bind.tput"] == pytest.approx(
        1000.0 * wave["block_decode/model"] / BINDS, rel=1e-9)
    assert got["unscoped_device_share.tput"] == pytest.approx(
        100.0 * (wave["(no scope)"] + prefix["(no scope)"]) / want["busy_s"], rel=1e-9)
    assert got["unscoped_device_share.tput"] == pytest.approx(100.0 * want["no_scope"]["share"], rel=1e-9)
    # the toy program of the fixture has no such scope: nothing to read is nothing, not 0
    assert got["attn_device_ms_per_bind.tput"] is None
    assert got["kv_writeback_device_ms_per_bind.tput"] is None
    assert got["lm_head_device_ms_per_bind.tput"] is None
    # a scope a later architecture names is asked for the same way; a
    # kernel's name ends the path of its own operation
    from metrics import _scope_trace as st

    assert st.per_bind_ms(ctx, "toy_kernel") == pytest.approx(
        1000.0 * wave["block_decode/model/toy_kernel"] / BINDS, rel=1e-9)
    # under a scope: all it holds; its own: what it does beside them
    decode = [s for path, s in wave.items() if path.split("/")[0] == "block_decode"]
    assert len(decode) == 3
    assert st.seconds_under(ctx, "block_decode") == pytest.approx(sum(decode), rel=1e-9)
    assert st.seconds_under(ctx, "model") == pytest.approx(sum(decode), rel=1e-9)
    assert st.seconds_under(ctx, "model", own=True) == pytest.approx(wave["block_decode/model"], rel=1e-9)
    assert st.seconds_under(ctx, "block_decode", own=True) is None
    assert st.seconds_under(ctx, "decode") is None  # a whole component, not a part of its name


@pytest.mark.parametrize("path", [DATA / "small_trace.xplane.pb", None])
def test_a_trace_without_scopes_or_no_trace_file_reads_none(path):
    """Executables from a compile cache older than the scopes, or a
    harness that hands no `xplane_path`: every reader returns None, never
    0 and never "100% unscoped"."""
    import run as bench_run

    ctx = context(path)
    assert all(bench_run.reader_for(n)(ctx) is None for n in NAMES)


def test_scope_of_drops_the_compilers_structure():
    from harness import scopes

    assert scopes.scope_of("jit(wave)/jit(main)/block_decode/while/body/model/mlp/dot_general:") == (
        "block_decode/model/mlp", "dot_general")
    assert scopes.scope_of("jit(wave)/jit(main)/while/body/closed_call/...d,df->...f/dot_general") == (
        "", "dot_general")
    assert scopes.scope_of("") == ("", "")


@pytest.mark.parametrize("reader_raises", [False, True])
def test_the_capture_outlives_the_readers_and_no_longer(tmp_path, reader_raises):
    import run as bench_run

    log_dir = tmp_path / "trace-cell"
    held = log_dir / "plugins" / "profile" / "2026_01_01" / "host.xplane.pb"
    held.parent.mkdir(parents=True)
    tracer = bench_run.Tracer(log_dir, SimpleNamespace(tracing=False), 6.0)
    assert not log_dir.exists()  # a capture left by an earlier run is cleared first
    held.parent.mkdir(parents=True)
    shutil.copy(SCOPED, held)

    def readers():
        with tracer.capture() as (path, profile):
            assert path == str(held) and held.exists()
            assert any(p.name.startswith("/device:TPU:") for p in profile.planes)
            from metrics import _scope_trace as st

            assert st.reduced(context(path)) is not None  # a reader opens the file itself
            if reader_raises:
                raise RuntimeError("a reader raised")

    if reader_raises:
        with pytest.raises(RuntimeError, match="a reader raised"):
            readers()
    else:
        readers()
    assert not log_dir.exists()
