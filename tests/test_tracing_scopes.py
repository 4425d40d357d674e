"""The names the program gives its own time (PR 25), held in place:

- device: the named scopes in the lowered serving programs, the program
  name of every serving `jax.jit`, the `name=` of every Pallas kernel;
- host: the annotation sink of `observability/spans` (layer prefix, the
  shared no-op when disabled), the engine worker's spans, and the wave
  number that ties a decision's trace to the device program run that
  served it;
- the reduction of a recorded trace by scope (`observability/scopes.py`,
  what `tools/trace_scopes.py` prints), against the benchmark's own
  reduction of the same file.

One tiny real engine is compiled for the module (CPU, a few seconds).
"""

from __future__ import annotations

import ast
import json
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from k8s_llm_scheduler_tpu.observability import scopes, spans
from k8s_llm_scheduler_tpu.testing import synthetic_cluster
from conftest import make_pod

REPO = Path(__file__).resolve().parents[1]
PACKAGE = REPO / "k8s_llm_scheduler_tpu"
BENCH = REPO / "benchmark"
# the names the benchmark wraps around the program from outside and matches
# by exact name (benchmark/harness/system.py HOST_SPANS)
HARNESS_SPANS = {"snapshot", "decide", "submit_wave", "harvest_wave", "prefix_prefill", "bind"}
PROGRAM_NAMES = {
    "_wave": "wave", "_prefill": "prefill", "_prefill_kv": "prefix_prefill_kv",
    "_suffix_dense": "suffix_dense", "_admit": "admit", "_chunk": "decode_chunk",
    "_fused_chunk": "fused_decode_chunk",
}


class RecordingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: keeps what was entered."""

    seen: list["RecordingAnnotation"] = []
    lock = threading.Lock()

    def __init__(self, name, **stats):
        self.name, self.stats, self.thread = name, dict(stats), threading.get_ident()

    def __enter__(self):
        with self.lock:
            self.seen.append(self)
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        self.stats.update(stats)


@pytest.fixture
def annotations(monkeypatch):
    RecordingAnnotation.seen = []
    monkeypatch.setattr(spans, "_TraceAnnotation", RecordingAnnotation)
    return RecordingAnnotation.seen


@pytest.fixture
def recorder(monkeypatch):
    rec = spans.FlightRecorder(capacity=64)
    monkeypatch.setattr(spans, "flight", rec)
    spans.configure(enabled=True)
    yield rec
    spans.configure(enabled=True)


@pytest.fixture(scope="module")
def backend():
    from k8s_llm_scheduler_tpu.engine.local import build_local_backend
    from k8s_llm_scheduler_tpu.models.configs import LlamaConfig

    cfg = LlamaConfig(
        name="scopes-test", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=128, max_seq_len=4096, rope_theta=10000.0,
        dtype=jnp.float32, tie_embeddings=True,
    )
    backend = build_local_backend(
        cfg=cfg, max_slots=4, num_pages=64, page_size=64,
        prefill_buckets=(512, 1024, 2048), chunk_steps=8, temperature=0.0,
        max_new_tokens=96, max_reason_tokens=4,
    )
    # keep the arguments of the wave program's calls: the scope test lowers
    # the program again with exactly what serving passed it
    engine, wave = backend.engine, backend.engine._wave
    backend.wave_program, backend.wave_calls = wave, []

    def recording_wave(*args):
        backend.wave_calls.append(args)
        return wave(*args)

    recording_wave.lower = wave.lower
    engine._wave = recording_wave
    yield backend
    backend.close()


def holds_scope(lowered_text: str, scope: str) -> bool:
    """A scope is a path component of some operation's location; the body
    of a layer scan is a function of its own, whose locations start at the
    scope (`"mlp/dot_general"`) and are prefixed where it is called."""
    return f"/{scope}/" in lowered_text or f'"{scope}/' in lowered_text


def decide_all(backend, nodes, pods):
    """Each decision under its own trace, from its own thread, as the
    scheduler's tasks would send them; returns the traces."""

    def one(pod):
        with spans.start_trace("decision", layer="sched", pod=pod.name) as trace:
            backend.get_scheduling_decision(pod, nodes)
        return trace

    with ThreadPoolExecutor(max_workers=len(pods)) as pool:
        return list(pool.map(one, pods))


# ------------------------------------------------------------------ device
class TestDeviceNames:
    def test_serving_programs_are_named(self, backend):
        engine = backend.engine
        for attr, name in PROGRAM_NAMES.items():
            fn = backend.wave_program if attr == "_wave" else getattr(engine, attr)
            assert fn.__name__ == name, attr

    def test_lowered_programs_hold_the_scopes(self, backend, recorder):
        engine = backend.engine
        nodes = synthetic_cluster(3).get_node_metrics()
        decide_all(backend, nodes, [make_pod(name="scope-pod")])
        assert backend.wave_calls, "no wave program ran"
        text = engine._wave.lower(*backend.wave_calls[-1]).as_text(debug_info=True)
        assert "module @jit_wave " in text
        for scope in ("suffix_prefill", "block_decode", "sample_expand", "model",
                      "kv_writeback", "embed", "attn", "mlp", "lm_head"):
            assert holds_scope(text, scope), scope
        # the model call sits under block_decode/.../model, the layers' matmuls under mlp
        assert any("block_decode/while/body/model/" in l for l in text.splitlines())
        assert any('"mlp/' in l and "dot_general" in l for l in text.splitlines())

        tokens = jnp.zeros((1, 512), jnp.int32)
        text = engine._prefill_kv.lower(engine.params, engine.cfg, tokens, jnp.asarray([7])).as_text(
            debug_info=True)
        assert "module @jit_prefix_prefill_kv " in text
        for scope in ("prefix_prefill", "attn", "mlp", "embed"):
            assert holds_scope(text, scope), scope
        assert not holds_scope(text, "lm_head")  # KV only

        prefix = engine._prefix
        text = engine._suffix_dense.lower(
            engine.params, engine.cfg, tokens, jnp.asarray([7], jnp.int32),
            prefix.k, prefix.v, jnp.int32(prefix.length),
        ).as_text(debug_info=True)
        assert "module @jit_suffix_dense " in text
        for scope in ("attn", "mlp", "lm_head"):
            assert holds_scope(text, scope), scope

    def test_each_pallas_call_has_its_name(self):
        """The `name=` is the stem of the compiled operation's name, which
        is how a device trace's reader finds the kernel
        (benchmark/metrics/prefix_attn_roofline.py KERNEL)."""
        from k8s_llm_scheduler_tpu.ops import pallas_paged_attention as paged
        from k8s_llm_scheduler_tpu.ops import pallas_prefix_attention as prefix
        from k8s_llm_scheduler_tpu.ops.ragged_matmul import ragged_matmul

        def kernel_names(fn, *args):
            found = []

            def walk(jaxpr):
                for eqn in jaxpr.eqns:
                    if eqn.primitive.name == "pallas_call":
                        found.append(eqn.params["name"])
                    for value in eqn.params.values():
                        inner = getattr(value, "jaxpr", value)
                        if hasattr(inner, "eqns"):
                            walk(inner)

            walk(jax.make_jaxpr(fn)(*args).jaxpr)
            return found

        bf = jnp.bfloat16
        q4 = jnp.zeros((2, 128, 4, 64), bf)
        kv = jnp.zeros((2, 128, 2, 64), bf)
        pk = jnp.zeros((128, 2, 64), bf)
        q3 = jnp.zeros((2, 4, 64), bf)
        cache = jnp.zeros((4, 64, 2, 64), bf)
        table, lens = jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32)
        assert kernel_names(
            lambda: prefix.flash_prefix_attention_parts(q4, pk, pk, jnp.int32(100), interpret=True)
        ) == ["flash_prefix_attention_parts"]
        assert kernel_names(
            lambda: prefix.flash_causal_attention_parts(q4, kv, kv, lens * 100, interpret=True)
        ) == ["flash_causal_attention_parts"]
        assert kernel_names(
            lambda: paged.paged_decode_attention_pallas(q3, cache, cache, table, lens, interpret=True)
        ) == ["paged_decode_attention_pallas"]
        assert kernel_names(
            lambda: paged.paged_decode_attention_parts(q3, cache, cache, table, lens, interpret=True)
        ) == ["paged_decode_attention_parts"]
        assert kernel_names(
            lambda: ragged_matmul(jnp.zeros((64, 128), bf), jnp.zeros((128, 256), bf), jnp.int32(3),
                                  interpret=True)
        ) == ["ragged_matmul"]


# -------------------------------------------------------------------- host
class TestSpanSink:
    def test_annotation_carries_the_layer_and_the_flight_recorder_does_not(self, recorder, annotations):
        with spans.start_trace("decision", layer="sched", pod="ns/p") as trace:
            with spans.span("decide", layer="sched", attempt=1):
                pass
        with spans.thread_span("queue_wait", layer="engine", wave=3) as ann:
            ann.set_metadata(model_calls=9)
        with spans.span("prefix_prefill", layer="engine", tokens=5) as sp:
            assert sp is None  # no ambient trace: the annotation alone
        assert [a.name for a in annotations] == [
            "sched.decision", "sched.decide", "engine.queue_wait", "engine.prefix_prefill"]
        assert annotations[0].stats == {"trace": trace.trace_id}
        assert annotations[1].stats == {"trace": trace.trace_id, "attempt": 1}
        assert annotations[2].stats == {"wave": 3, "model_calls": 9}
        assert [s.name for s in trace.spans] == ["decision", "decide"]
        assert spans.annotation_name("learn", "learn.mine") == "learn.mine"

    def test_disabled_is_the_shared_noop(self, recorder, annotations, monkeypatch):
        spans.configure(enabled=False)

        def boom(*a, **k):
            raise AssertionError("an annotation or a span was allocated while disabled")

        monkeypatch.setattr(spans, "_annotation", boom)
        monkeypatch.setattr(spans, "Span", boom)
        monkeypatch.setattr(spans, "Trace", boom)
        assert spans.span("decide", layer="sched") is spans._NULL
        assert spans.thread_span("tick", layer="engine", wave=1) is spans._NULL
        assert spans.start_trace("decision", layer="sched") is spans._NULL
        with spans.span("decide", layer="sched") as sp, spans.thread_span("tick", layer="engine") as ann:
            assert sp is None and ann is None
        assert annotations == [] and recorder.stats()["recorded"] == 0

    def test_a_sink_takes_each_span_s_seconds_by_name(self, recorder, annotations):
        """`thread_span(..., sink=)` is the set-up record's one way in: the
        annotation as any thread span, and on exit the block's seconds
        added to `sink[name]`."""
        record: dict[str, float] = {}
        with spans.thread_span("setup_build", layer="engine", sink=record):
            with spans.thread_span("setup_params", layer="engine", sink=record) as ann:
                ann.set_metadata(where="inner")
                time.sleep(0.01)
        assert [a.name for a in annotations] == ["engine.setup_build", "engine.setup_params"]
        assert record["setup_build"] >= record["setup_params"] >= 0.01
        first = record["setup_params"]
        with spans.thread_span("setup_params", layer="engine", sink=record):
            pass
        assert record["setup_params"] >= first  # added, not replaced

    def test_a_sink_keeps_its_record_while_tracing_is_off(self, recorder, annotations, monkeypatch):
        spans.configure(enabled=False)
        monkeypatch.setattr(spans, "_annotation", lambda *a, **k: pytest.fail("annotated while off"))
        record: dict[str, float] = {}
        with spans.thread_span("setup_build", layer="engine", sink=record) as ann:
            assert ann is None
        assert set(record) == {"setup_build"} and annotations == []

    def test_build_local_backend_records_its_set_up(self, backend):
        """`engine.setup_params` lies inside `engine.setup_build`, and both
        reach `get_stats()["setup"]` beside the compile log's books."""
        setup = backend.get_stats()["setup"]
        assert 0.0 < setup["params_s"] <= setup["build_s"]
        assert setup["build_s"] == backend.setup["setup_build"]
        assert setup["programs"] >= 1 and setup["trace_lower_s"] > 0.0

    def test_every_call_site_names_its_layer(self):
        """Over every `spans.span(` / `spans.thread_span(` /
        `spans.start_trace(` of the package: a literal `layer=` of the
        program's own, so that no annotation the program writes can be
        one of the benchmark's six bare names."""
        sites = 0
        for path in sorted(PACKAGE.rglob("*.py")):
            if path.name == "spans.py":
                continue
            for node in ast.walk(ast.parse(path.read_text())):
                if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                        and node.func.attr in ("span", "thread_span", "start_trace")
                        and isinstance(node.func.value, ast.Name) and node.func.value.id == "spans"):
                    continue
                where = f"{path.relative_to(REPO)}:{node.lineno}"
                layer = next((k.value for k in node.keywords if k.arg == "layer"), None)
                assert isinstance(layer, ast.Constant) and layer.value in spans.LAYERS[:3], where
                assert node.args and isinstance(node.args[0], ast.Constant), where
                written = spans.annotation_name(layer.value, node.args[0].value)
                assert written.startswith(layer.value + ".") and written not in HARNESS_SPANS, where
                sites += 1
        assert sites >= 40


class TestWaveNumber:
    def test_items_and_traces_carry_the_wave_that_served_them(self, backend, recorder, annotations):
        engine = backend.engine
        nodes = synthetic_cluster(3).get_node_metrics()
        first = engine.stats.get("waves", 0) + 1
        traces = decide_all(backend, nodes, [make_pod(name=f"wave-a-{i}") for i in range(3)])
        traces += decide_all(backend, nodes, [make_pod(name=f"wave-b-{i}") for i in range(2)])
        by_wave: dict[int, list] = {}
        for trace in traces:
            by_name = {s.name: s for s in trace.spans}
            assert {"admission_wait", "wave", "prefill", "decode"} <= set(by_name)
            wave = trace.meta["wave"]
            assert by_name["admission_wait"].attrs["wave"] == wave
            assert by_name["wave"].attrs["wave"] == wave
            assert by_name["wave"].attrs["model_calls"] >= 1
            assert by_name["wave"].attrs["suffix_tokens"] == by_name["prefill"].attrs["tokens"]
            assert by_name["wave"].attrs["served_tokens"] == by_name["decode"].attrs["tokens"]
            # measured, and the two apportioned spans still split exactly it
            assert "apportioned" not in by_name["wave"].attrs
            assert by_name["prefill"].attrs["apportioned"] and by_name["decode"].attrs["apportioned"]
            assert by_name["prefill"].dur_ms + by_name["decode"].dur_ms == pytest.approx(
                by_name["wave"].dur_ms)
            by_wave.setdefault(wave, []).append(by_name["wave"].attrs["rows"])
        # every item of a wave agrees on it; the waves are numbered on from
        # the engine's count, the second batch after the first
        for wave, rows in by_wave.items():
            assert rows == [len(rows)] * len(rows), (wave, rows)
        assert sorted(by_wave) == list(range(first, first + len(by_wave)))
        assert len(by_wave) >= 2 and engine.stats["waves"] == first + len(by_wave) - 1
        assert max(t.meta["wave"] for t in traces[:3]) < min(t.meta["wave"] for t in traces[3:])

        # the same numbers on the worker thread's annotations
        names = {a.name for a in annotations}
        assert {"engine.queue_wait", "engine.tick", "engine.submit_wave", "engine.dispatch",
                "engine.harvest_poll", "engine.harvest_wave", "engine.harvest_wait", "engine.resolve",
                "sched.render", "sched.tokenize", "sched.decision"} <= names
        assert all(n.startswith(("engine.", "sched.")) for n in names)
        submits = [a for a in annotations if a.name == "engine.submit_wave"]
        harvests = [a for a in annotations if a.name == "engine.harvest_wave"]
        assert [a.stats["wave"] for a in submits] == sorted(by_wave)
        assert [a.stats["wave"] for a in harvests] == sorted(by_wave)
        assert all(a.stats["rows"] == len(by_wave[a.stats["wave"]]) and a.stats["bucket"] == 512
                   for a in submits)
        assert all(a.stats["model_calls"] >= 1 for a in harvests)
        worker = {a.thread for a in annotations if a.name.startswith("engine.")}
        assert len(worker) == 1 and worker.isdisjoint(
            {a.thread for a in annotations if a.name.startswith("sched.")})


# ------------------------------------------------- the recorded trace
SCOPED = BENCH / "tests" / "data" / "scoped_trace.xplane.pb"


class TestScopeReduction:
    """`tools/trace_scopes.py`'s reduction on the trace that
    benchmark/tests/record_scoped_fixture.py took on a TPU v5 lite, and its
    agreement with the benchmark's reduction of the same file."""

    @pytest.fixture(scope="class")
    def reduced(self):
        return scopes.reduce_scopes(str(SCOPED))

    @pytest.fixture(scope="class")
    def expected(self):
        return json.loads(SCOPED.with_name("scoped_trace.expected.json").read_text())

    def test_seconds_by_scope_path(self, reduced, expected):
        want = expected["program"]
        assert reduced["measured"]
        assert reduced["programs"].keys() == want["programs"].keys() >= {"wave", "prefix_prefill_kv"}
        assert reduced["programs"]["wave"]["runs"] == 3
        for program, per in want["scopes"].items():
            assert reduced["scopes"][program] == pytest.approx(per, rel=1e-9)
        wave = reduced["scopes"]["wave"]
        assert {"suffix_prefill/mlp", "block_decode/model/mlp"} <= set(wave)
        # the loop runs the layers five times for the prefill's once
        assert wave["block_decode/model/mlp"] == pytest.approx(5 * wave["suffix_prefill/mlp"], rel=0.1)
        assert reduced["scopes"]["prefix_prefill_kv"].keys() >= {"prefix_prefill/mlp"}
        # the kernel is found by its name, under the scope it runs in
        assert reduced["kernels"].keys() == {"toy_kernel"}
        assert any(s.startswith("block_decode/model") and "toy_kernel" in o["op"]
                   for o in reduced["ops"] for s in [o["scope"]])
        assert reduced["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
        assert 0.0 <= reduced["no_scope"]["share"] < 0.2

    def test_block_decode_is_the_time_inside_while_in_both_reductions(self, reduced, expected):
        sys.path.insert(0, str(BENCH))
        try:
            from harness import xplane
            from metrics import _program_trace
        finally:
            sys.path.remove(str(BENCH))
        runs = _program_trace.module_runs(xplane.load(str(SCOPED)))
        bd = reduced["block_decode"]
        assert bd["scope_s"] == pytest.approx(bd["while_s"], rel=0.01)
        # the benchmark splits each wave run at the same `while`
        assert bd["while_s"] == pytest.approx(expected["benchmark"]["decode_s"], rel=1e-4)
        assert reduced["programs"]["wave"]["seconds"] == pytest.approx(
            sum(b - a for a, b, name in runs if name == "jit_wave") * 1e-9, rel=1e-4)

    def test_a_trace_without_scopes_is_not_measured(self, capsys):
        sys.path.insert(0, str(REPO / "tools"))
        try:
            import trace_scopes
        finally:
            sys.path.remove(str(REPO / "tools"))
        plain = BENCH / "tests" / "data" / "small_trace.xplane.pb"
        reduced = scopes.reduce_scopes(str(plain))
        assert not reduced["measured"] and reduced["no_scope"]["share"] == pytest.approx(1.0)
        text = trace_scopes.render(reduced)
        assert "not measured" in text and "unscoped" not in text and "no scope" not in text.lower().replace(
            "by scope: not measured", "")
        assert "block_decode" in trace_scopes.render(scopes.reduce_scopes(str(SCOPED)))
