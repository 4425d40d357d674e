"""Records the small trace that both reductions of named device and host
time are checked against (tests/data/scoped_trace.xplane.pb with
scoped_trace.expected.json) and prints what it holds. Run on the chip:

    python3 benchmark/tests/record_scoped_fixture.py chiprun_out/scoped_fixture

The shape of the program's own trace at a toy size: a jitted program named
`wave` with scope `suffix_prefill` (a layer scan: one `while`) and scope
`block_decode` (a `while_loop` whose body holds scope `model`, the layer
scan again with `mlp` inside, and a Pallas kernel that carries a `name=`
and, as Mosaic kernels do, no scope of its own); a second program named
`prefix_prefill_kv`; a worker thread that submits and harvests three waves
under `engine.*` annotations with a `wave` stat; `sched.*` annotations on
the main thread. The benchmark's reduction (metrics/_program_trace.py) and
the program's (k8s_llm_scheduler_tpu/observability/scopes.py) both read it;
what they found here is written beside it, and tests hold them to it.
"""

from __future__ import annotations

import json
import shutil
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

LAYERS, WIDTH, ROWS, ITERS, WAVES = 4, 2048, 256, 5, 3


def build():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def layers(x, ws):
        def layer(c, w):
            with jax.named_scope("mlp"):
                return jnp.tanh(c @ w).astype(c.dtype), None

        return jax.lax.scan(layer, x, ws)[0]

    def wave(x, ws):
        with jax.named_scope("suffix_prefill"):
            x = layers(x, ws)

        def body(state):
            i, x = state
            with jax.named_scope("model"):
                x = layers(x, ws)
                x = pl.pallas_call(
                    double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype), name="toy_kernel",
                    interpret=jax.default_backend() != "tpu",
                )(x) * 0.5
            return i + 1, x

        with jax.named_scope("block_decode"):
            _, x = jax.lax.while_loop(lambda s: s[0] < ITERS, body, (jnp.int32(0), x))
        return x

    def prefix_prefill_kv(x, ws):
        with jax.named_scope("prefix_prefill"):
            return layers(x, ws)

    x = jnp.ones((ROWS, WIDTH), jnp.bfloat16)
    ws = jnp.full((LAYERS, WIDTH, WIDTH), 0.01, jnp.bfloat16)
    return jax.jit(wave), jax.jit(prefix_prefill_kv), x, ws


def drive(wave, prefix, x, ws) -> None:
    """What is traced: three waves from a worker thread, the prefix program
    and the `sched.*` spans from this one."""
    from jax.profiler import TraceAnnotation as Span

    def worker() -> None:
        for k in range(1, WAVES + 1):
            with Span("engine.queue_wait"):
                time.sleep(0.004)
            with Span("engine.tick"):
                with Span("engine.submit_wave", wave=k, rows=8, bucket=128):
                    time.sleep(0.0005)
                    with Span("engine.dispatch", wave=k):
                        y = wave(x, ws)
                with Span("engine.harvest_poll", wave=k):
                    time.sleep(0.002)
                with Span("engine.harvest_wave", wave=k, rows=8, bucket=128) as ann:
                    with Span("engine.harvest_wait", wave=k):
                        y.block_until_ready()
                    ann.set_metadata(model_calls=ITERS)
                    time.sleep(0.001)
                with Span("engine.resolve", wave=k):
                    time.sleep(0.001)

    thread = threading.Thread(target=worker, name="engine-worker")
    thread.start()
    for pod in range(2):
        with Span("sched.decision", trace=f"t-{pod}"):
            with Span("sched.render"):
                time.sleep(0.001)
                with Span("sched.delta_encode"):
                    time.sleep(0.001)
            with Span("sched.tokenize"):
                time.sleep(0.001)
            if pod == 0:
                with Span("sched.bind_call"):
                    prefix(x, ws).block_until_ready()
    thread.join()


def reductions(path: str) -> dict:
    """Both reductions of the recorded file, as tests compare them."""
    from harness import xplane
    from metrics import _program_trace as pt

    from k8s_llm_scheduler_tpu.observability import scopes

    profile = xplane.load(path)
    # the benchmark's: a context with 8 binds inside the slice
    ctx = SimpleNamespace(profile=profile, outcome=SimpleNamespace(trace_span=(0.0, 1.0)),
                          cluster=SimpleNamespace(acks={f"pod-{i}": 0.1 * i for i in range(8)}))
    bench = pt.reduced(ctx)
    threads = pt.host_spans(profile, ("engine.",))
    worker = max(threads.values(), key=len)
    return {
        "benchmark": bench,
        "worker_self_ns": [[n, t] for n, t in pt.self_times(worker)],
        "worker_stats": [[r[2], {k: v for k, v in r[3].items() if k in ("wave", "rows", "bucket", "model_calls")}]
                         for r in worker],
        "program": scopes.reduce_scopes(path),
    }


def main() -> int:
    import jax

    from harness import xplane

    out = Path(sys.argv[1])
    shutil.rmtree(out, ignore_errors=True)
    wave, prefix, x, ws = build()
    wave(x, ws).block_until_ready()
    prefix(x, ws).block_until_ready()
    jax.profiler.start_trace(str(out))
    drive(wave, prefix, x, ws)
    jax.profiler.stop_trace()
    path = xplane.find_xplane(str(out))
    profile = xplane.load(path)
    planes = {p.name: {l.name: len(list(l.events)) for l in p.lines} for p in profile.planes}
    print(json.dumps({"file": path, "bytes": Path(path).stat().st_size, "planes": planes})[:6000])
    shutil.copy(path, out / "scoped_trace.xplane.pb")
    shutil.rmtree(out / "plugins", ignore_errors=True)
    found = reductions(str(out / "scoped_trace.xplane.pb"))
    (out / "scoped_trace.expected.json").write_text(json.dumps(found, indent=1) + "\n")
    print(json.dumps(found))
    return 0


if __name__ == "__main__":
    sys.exit(main())
