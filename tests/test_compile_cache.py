"""The compile-cache resolver (utils/compile_cache.py): placed from outside
by JAX's own variable, else at a fixed path inside the checkout.

enable_persistent_compile_cache() returns early on the cpu backend, so these
exercise the RESOLVER and, for the "sets nothing in code" rule, the config
calls the enabler makes."""

import os
from pathlib import Path

import jax
import numpy as np

from k8s_llm_scheduler_tpu.utils import compile_cache as cc

REPO = Path(__file__).resolve().parent.parent


def test_env_var_wins_and_nothing_is_set_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path / "outside"))
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append(name)
    )
    for path in ("auto", "/somewhere/else"):
        assert cc.resolve_compile_cache_dir(path) == str(tmp_path / "outside")
        assert cc.enable_persistent_compile_cache(path) == str(tmp_path / "outside")
    assert updates == []
    assert not (tmp_path / "outside").exists()  # JAX's to create, not ours


def test_unset_resolves_inside_the_checkout(monkeypatch):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    assert cc.resolve_compile_cache_dir("auto") == str(REPO / ".xla_cache")
    assert cc.resolve_compile_cache_dir() == str(REPO / ".xla_cache")
    assert cc.resolve_compile_cache_dir("/explicit/dir") == "/explicit/dir"
    assert ".xla_cache/" in (REPO / ".gitignore").read_text().split()


def test_same_path_from_any_cwd_and_pid(monkeypatch, tmp_path):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    here = cc.resolve_compile_cache_dir("auto")
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(os, "getpid", lambda: 4242)
    monkeypatch.setenv("HOME", str(tmp_path))
    assert cc.resolve_compile_cache_dir("auto") == here
    assert os.path.isabs(here) and str(tmp_path) not in here


def test_none_disables(monkeypatch, tmp_path):
    for env in (None, str(tmp_path)):
        if env is None:
            monkeypatch.delenv(cc.ENV_VAR, raising=False)
        else:
            monkeypatch.setenv(cc.ENV_VAR, env)
        for path in (None, ""):
            assert cc.resolve_compile_cache_dir(path) is None
            assert cc.enable_persistent_compile_cache(path) is None


def test_accelerator_backend_sets_the_resolved_dir(monkeypatch, tmp_path):
    """Off the cpu backend and with the variable unset, the enabler is the
    one place the directory is set."""
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append((name, value))
    )
    target = str(tmp_path / "cache")
    assert cc.enable_persistent_compile_cache(target) == target
    assert updates == [("jax_compilation_cache_dir", target)]
    assert (tmp_path / "cache").is_dir()


# ------------------------------------------------------------ the compile log
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

TRACE, LOWER = "/jax/core/compile/jaxpr_trace_duration", "/jax/core/compile/jaxpr_to_mlir_module_duration"


@pytest.fixture
def log():
    """A log of its own, its listeners taken off again at the end (the
    process's COMPILE_LOG keeps its own for the process's life)."""
    import jax.monitoring as mon

    fresh = cc.CompileLog().install()
    yield fresh
    mon.unregister_event_listener(fresh._event)
    mon.unregister_event_duration_listener(fresh._duration)
    mon.unregister_event_time_span_listener(fresh._time_span)


def test_an_enclosing_interval_takes_back_the_ones_it_holds():
    """Events arrive at their end, the innermost first: [1, 2] and [3, 5]
    inside [0, 10] book 10 s, not 13; a later [11, 12] adds its own; a hit
    and the seconds before a program go to the next program built."""
    log = cc.CompileLog()
    for start, end in ((1.0, 2.0), (3.0, 5.0), (0.0, 10.0), (11.0, 12.0)):
        log._time_span(TRACE if start != 11.0 else LOWER, start, end, fun_name="x")
    assert log.trace_lower_s == pytest.approx(11.0)
    log._event(cc._HIT)
    log._duration(cc._RETRIEVAL, 0.25)
    log._duration(cc._BUILD, 0.5, fun_name="jit(wave)")
    log._duration(cc._BUILD, 2.0, fun_name="jit(wave)")
    assert log.programs == [cc.Program("jit(wave)", 11.0, 0.5, True), cc.Program("jit(wave)", 0.0, 2.0, False)]
    assert log.books() == {"programs": 2, "programs_compiled": 1, "programs_loaded": 1,
                           "trace_lower_s": 11.0, "load_compile_s": 2.5, "retrieval_s": 0.25}
    assert log.table() == {"jit(wave)": {"count": 2, "compiled": 1, "loaded": 1,
                                         "trace_lower_s": 11.0, "load_compile_s": 2.5}}
    assert log.table(1) == {"jit(wave)": {"count": 1, "compiled": 1, "loaded": 0,
                                          "trace_lower_s": 0.0, "load_compile_s": 2.0}}


def test_a_jit_that_calls_jits_books_its_trace_once(log):
    """`outer` traces `inner` twice and jnp's own jitted helpers inside
    that: every nested trace fires an event of its own, inside the outer
    trace's span. The log books the outermost spans alone."""
    import jax.monitoring as mon

    seen = []

    def listen(name, start, end, **kw):
        if name in (TRACE, LOWER):
            seen.append((start, end))

    mon.register_event_time_span_listener(listen)
    try:
        inner = jax.jit(lambda x: jnp.tanh(x) @ x)
        outer = jax.jit(lambda x: inner(x).sum() + inner(2 * x).sum())
        x = np.ones((4, 4), np.float32)
        before = log.books()
        outer(x).block_until_ready()
    finally:
        mon.unregister_event_time_span_listener(listen)
    outermost = [(s, e) for s, e in seen
                 if not any(s2 <= s and e <= e2 and (s2, e2) != (s, e) for s2, e2 in seen)]
    assert len(seen) > len(outermost) >= 2  # nested traces fired, and a trace + a lowering lie outermost
    booked = log.books()["trace_lower_s"] - before["trace_lower_s"]
    assert booked == pytest.approx(sum(e - s for s, e in outermost))
    assert booked < sum(e - s for s, e in seen)
    row = log.table(before["programs"])["jit(<lambda>)"]
    assert row["count"] == 1 and row["trace_lower_s"] == pytest.approx(booked)


@pytest.fixture
def cache_dir(tmp_path):
    """The persistent cache on, in a directory of the test's own, for
    every program; put back as it was afterwards."""
    from jax._src import compilation_cache

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    for key, value in zip(keys, (str(tmp_path), 0.0, 0)):
        jax.config.update(key, value)
    yield tmp_path
    for key, value in was.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()


def test_a_miss_then_a_hit_go_to_the_program_s_name(log, cache_dir):
    def miss_then_hit(x):
        return jnp.cos(x) * 3.0 + x.sum()

    program = jax.jit(miss_then_hit)
    x = np.arange(8, dtype=np.float32)
    first = log.books()["programs"]
    program(x).block_until_ready()
    jax.clear_caches()  # the process forgets it; the cache on disk does not
    program(x).block_until_ready()
    rows = log.table(first)
    row = rows["jit(miss_then_hit)"]
    assert row["count"] == 2 and row["compiled"] == 1 and row["loaded"] == 1
    assert row["trace_lower_s"] > 0.0  # traced and lowered again on the hit
    assert [p.loaded for p in log.programs[first:] if p.name == "jit(miss_then_hit)"] == [False, True]
    assert log.books()["retrieval_s"] > 0.0


def test_warm_calls_leave_every_book_as_it_was(log):
    program = jax.jit(lambda x: jnp.sin(x) + 1.0)
    x = jnp.ones((16,))
    program(x).block_until_ready()
    before, n = log.books(), len(log.programs)
    for _ in range(100):
        program(x)
    program(x).block_until_ready()
    assert log.books() == before and len(log.programs) == n


def test_the_set_up_record_becomes_a_handful_of_gauges():
    """`get_stats()["setup"]`: the four spans' seconds and the log's totals,
    no per-program table, so `/metrics` gains ten gauges and no name per
    program. The tokenizer's and the wait's spans lie inside the params'."""
    from k8s_llm_scheduler_tpu.engine.local import build_local_backend
    from k8s_llm_scheduler_tpu.observability.metrics import _flatten

    backend = build_local_backend(model="tiny", num_pages=16)
    try:
        setup = backend.get_stats()["setup"]
    finally:
        backend.close()
    for key in ("build_s", "params_s", "trace_lower_s", "load_compile_s", "programs_compiled"):
        assert isinstance(setup[key], (int, float)) and setup[key] >= 0, key
    assert setup["build_s"] >= setup["params_s"] > 0.0
    for key in ("tokenizer_s", "params_wait_s"):
        assert isinstance(setup[key], float) and setup[key] >= 0.0, key
    assert setup["tokenizer_s"] + setup["params_wait_s"] <= setup["params_s"]
    assert setup["programs"] == setup["programs_compiled"] + setup["programs_loaded"] >= 1
    gauges = {k: v for k, v in _flatten({"engine": {"setup": setup}}).items()}
    assert set(gauges) == {f"engine_setup_{k}" for k in (
        "build_s", "params_s", "tokenizer_s", "params_wait_s", "programs",
        "programs_compiled", "programs_loaded", "trace_lower_s", "load_compile_s", "retrieval_s")}
    assert gauges["engine_setup_build_s"] == setup["build_s"]
