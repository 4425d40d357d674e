"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the scheduler as `cli run --fake-cluster` does, warms every program
the cell's traffic can reach (set-up), drives `Scheduler.run()` with the
cell's traffic for `--seconds`, then frees the program and compares what the
window served with the plain reference. The last stdout line is the result;
the lines before it are the set-up breakdown and a summary. Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO))

TRACE_SECONDS = 6.0
SETUP_DEADLINE_S = 1100.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(workload: str) -> tuple[dict, dict, dict]:
    """(BENCHMARK.json, the cell, its configuration entry)."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    conf_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return bench, cell, conf_entry


def metrics_for(bench: dict, cell_name: str, group: str) -> list[dict]:
    return [m for m in bench[group] if cell_name in m.get("workloads", [cell_name])]


def reader_for(metric_name: str):
    """metrics/<name>.py, or metrics/<name without its last .suffix>.py:
    `wave_rows.tput` and `wave_rows.lat` share one reader."""
    for stem in (metric_name, metric_name.rsplit(".", 1)[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for per-layer metric {metric_name!r} under benchmark/metrics/")


class Tracer:
    """The JAX profiler around the last slice of the window."""

    def __init__(self, log_dir: Path, probes, seconds: float) -> None:
        self.log_dir, self.probes, self.seconds = log_dir, probes, seconds
        shutil.rmtree(log_dir, ignore_errors=True)

    def start(self) -> None:
        import jax

        self.probes.tracing = True
        jax.profiler.start_trace(str(self.log_dir))

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self.probes.tracing = False

    @contextlib.contextmanager
    def capture(self):
        """(path of the .xplane.pb, its ProfileData). The file stays on
        disk for as long as the block runs: an operation's scope is in the
        file's event metadata, which ProfileData does not surface, so the
        readers of metrics/_scope_trace.py open it themselves. Then the
        capture directory goes, also when a reader raises."""
        from harness import xplane

        try:
            path = xplane.find_xplane(str(self.log_dir))
            yield path, xplane.load(path)
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


class Ctx:
    """What a per-layer reader may look at."""

    def __init__(self, **kw) -> None:
        self.__dict__.update(kw)

    def delta(self, *path):
        a, b = self.outcome.after, self.outcome.before
        for key in path:
            a, b = a.get(key, 0) if isinstance(a, dict) else 0, b.get(key, 0) if isinstance(b, dict) else 0
        return a - b


def percentile(sorted_values: list[float], q: float) -> float:
    """Nearest rank."""
    return sorted_values[max(math.ceil(q * len(sorted_values)) - 1, 0)]


def free_program() -> None:
    """Drop the program's device state: every live array is deleted, so
    the reference starts from an empty chip."""
    import jax

    gc.collect()
    for arr in jax.live_arrays():
        arr.delete()
    jax.clear_caches()
    gc.collect()


def run_cell(cell: dict, conf: dict, bench: dict, seed: int, seconds: float, trace: bool,
             mix_override: dict | None = None, fault: str | None = None,
             control: bool = False, reference: bool = True) -> dict:
    """One run of one cell; returns the result object. `mix_override`
    (benchmark/rehearse.py, tests) swaps in a changed traffic mix; `fault`
    (benchmark/tests) breaks the timed path underneath; `control` judges
    the int8 control in the program's place; without `reference`
    (tests/try_cell.py) nothing is compared and `correct` is false."""
    import jax

    from harness import compare, device, system, window
    from harness import traffic as T

    mix = mix_override or T.load_traffic(cell["traffic"])
    # every program into the persistent cache (<checkout>/.xla_cache, where
    # the program puts it), not only those that took over a second to compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    dev = device.describe()
    compiles = device.CompileCounter()
    t_build0 = time.perf_counter()
    scheduler, backend, cluster, probes, cfg = system.build(conf, mix, seed)
    jax.block_until_ready(backend.engine.params)
    t_built = time.perf_counter()
    if trace:
        from k8s_llm_scheduler_tpu.observability import spans

        spans.configure(capacity=4096)  # the reader's ring holds the window
    stages = system.warm_up(backend, cluster, mix, seed, time.monotonic() + SETUP_DEADLINE_S)
    t_warm = time.perf_counter()
    setup_compiles = compiles.read()
    if fault is not None:
        from tests import faults

        faults.plant(fault, backend, probes)

    def snapshot() -> dict:
        return {"sched": scheduler.get_stats(), "compiles": compiles.read()}

    tracer = Tracer(REPO / ".bench_out" / f"trace-{cell['name']}", probes,
                    min(TRACE_SECONDS, seconds)) if trace else None
    out = asyncio.run(window.run_window(scheduler, cluster, mix, seed, seconds, snapshot, tracer))
    setup_s = out.t0 - T_PROCESS
    peak = device.memory_peak_bytes()
    compiles.active = False

    # ---- end-to-end, from the benchmark's own per-pod timestamps
    acks = cluster.acks
    sched_d = {k: out.after["sched"][k] - out.before["sched"][k]
               for k in ("llm_decisions", "cache_decisions", "fallback_decisions",
                         "unschedulable", "failed_bindings", "total_scheduled")}
    client_d = {k: out.after["sched"]["client"].get(k, 0) - out.before["sched"]["client"].get(k, 0)
                for k in ("failed_requests", "invalid_decisions", "deadline_timeouts")}
    wrong_source = (sched_d["fallback_decisions"] + sched_d["unschedulable"]
                    + sched_d["failed_bindings"] + sum(client_d.values()))
    e2e: dict[str, float] = {"setup_s": setup_s}
    if mix["kind"] == "closed_depth":
        bound = sum(1 for t in acks.values() if out.t0 <= t < out.t1)
        e2e["binds_per_s"] = bound / seconds
        attempted, failed = bound + wrong_source, wrong_source
    else:
        # due -> acknowledged for every pod due in the window, one bound after
        # the close included (its wait counts); only a pod that was never
        # bound, a minute past the close, misses
        lat, missed = [], 0
        for pod in out.window_pods:
            t = acks.get(pod)
            if t is None:
                missed += 1
                t = time.perf_counter()
            lat.append(1000.0 * (t - out.due[pod]))
        lat.sort()
        e2e["bind_p50_ms"] = percentile(lat, 0.50)
        e2e["bind_p95_ms"] = percentile(lat, 0.95)
        attempted, failed = len(lat), missed + wrong_source
    if mix["expect_source"] == "llm" and sched_d["cache_decisions"]:
        failed += sched_d["cache_decisions"]  # the cell says every decision reaches the engine

    # ---- per-layer, traced runs
    waves = probes.waves_between(out.t0, out.t1)
    per_layer: dict[str, float] = {}
    device_block = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
                    "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        from harness import xplane

        from k8s_llm_scheduler_tpu.observability import spans

        with tracer.capture() as (xplane_path, profile):
            reduced = xplane.reduce(profile)
            ta, tb = out.trace_span
            device_block["busy_s"] = reduced["busy_s"]
            device_block["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
            waits = [s["dur_ms"] for entry in spans.flight.export_slices()[0]
                     for s in entry["spans"] if s.get("name") == "admission_wait"]
            ctx = Ctx(conf=conf, mix=mix, cell=cell, outcome=out, seconds=seconds, waves=waves,
                      trace_waves=probes.waves_between(ta, tb), cluster=cluster, trace=reduced,
                      profile=profile, xplane_path=xplane_path, peaks=device.peaks(dev["kind"]),
                      chips=cell["chips"], prefix_prefills=probes.prefix_prefills,
                      admission_waits_ms=waits)
            for m in metrics_for(bench, cell["name"], "per_layer"):
                value = reader_for(m["name"])(ctx)
                if value is not None:
                    per_layer[m["name"]] = value

    # ---- free the program, then the reference
    node_names = [n.name for n in cluster.get_node_metrics()]
    bindings = {pod: node for _ns, pod, node in cluster.bindings}
    plans = {}
    if mix["kind"] == "closed_depth":
        plans = {p.name: p for p in T.closed_depth_pods(mix, seed)}
    else:
        for b in T.timetable(mix, seed, seconds)[1]:
            plans.update({p.name: p for p in b.pods})
    max_reason = min(int(cfg.get("llm.max_tokens")) - 62 - max(
        len(backend.tokenizer.encode(n)) for n in node_names), int(cfg.get("llm.max_reason_tokens")))
    summary = {
        "decisions": sched_d, "client": client_d, "waves": len(waves),
        "setup": {"imports_s": t_build0 - T_PROCESS, "build_and_init_s": t_built - t_build0,
                  "warm_up_s": t_warm - t_built, "warm_traffic_s": out.t0 - t_warm,
                  "stages": stages, "compiled": setup_compiles},
        "window_compiles": out.after["compiles"]["programs"] - out.before["compiles"]["programs"],
        "window_compiled_names": compiles.names_between(
            out.before["compiles"]["programs"], out.after["compiles"]["programs"]),
        "prefix_tokens": sorted({len(w["prefix_ids"]) for w in waves})[-3:],
        "prefix_buffers": sorted({w["prefix_cap"] for w in waves}),
        "engine": {k: out.after["sched"]["client"]["engine"].get(k, 0)
                   - out.before["sched"]["client"]["engine"].get(k, 0)
                   for k in ("waves", "wave_model_calls", "completed", "decode_tokens",
                             "prefill_tokens", "prefix_prefills", "prefix_hits")},
        "delta": out.after["sched"]["client"]["engine"].get("delta"),
    }
    if mix["kind"] == "timetable":
        # per burst: size, and due -> last bind acknowledged (None: not all bound)
        by_due: dict[float, list] = {}
        for pod in out.window_pods:
            by_due.setdefault(out.due[pod], []).append(acks.get(pod))
        summary["bursts"] = [[len(v), None if None in v else max(v) - due]
                             for due, v in sorted(by_due.items())]
    backend.close()
    t_ref0 = time.perf_counter()
    sample_pool = [dict(w) for w in waves]
    probes.waves.clear()
    del scheduler, backend, probes
    free_program()
    summary["end_to_end"] = e2e
    if reference:
        verdict = compare.compare(conf, seed, sample_pool, plans, node_names, bindings, max_reason,
                                  control=control)
        summary["reference_s"] = time.perf_counter() - t_ref0
        summary["sampled"] = verdict["sampled"]
        summary["program_gaps"] = verdict["program"]
        if control:
            summary["control_gaps"] = verdict["control"]
            summary["gap_lists"] = verdict["gap_lists"]
    else:
        verdict = {"correct": False, "compared": {}}

    metrics = per_layer if trace else e2e
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    wanted = {m["name"] for m in metrics_for(bench, cell["name"], "per_layer" if trace else "end_to_end")}
    result = {
        "correct": verdict["correct"] and failed == 0,
        "attempted": attempted, "failed": failed,
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in metrics.items() if k in wanted},
        "device": device_block,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["summary"] = summary
    result["compared"] = {**verdict["compared"], "failed_operations": {"value": failed, "limit": 0}}
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from harness import seam

    bench, cell, conf_entry = load_cell(args.workload)
    conf = seam.load_config(REPO / conf_entry["file"])
    try:
        import k8s_llm_scheduler_tpu  # noqa: F401
    except ImportError as exc:
        log(f"benchmark: the program is not in this directory ({exc}); nothing to measure")
        return 2
    import jax

    devs = jax.devices()
    where = f"platform={devs[0].platform} device_kind={devs[0].device_kind!r} count={len(devs)}"
    if devs[0].platform != "tpu":
        log(f"benchmark: no TPU: JAX came up on {where}; no result")
        return 3
    if len(devs) < cell["chips"]:
        log(f"benchmark: cell {cell['name']} needs {cell['chips']} chips, found {where}; no result")
        return 3
    log(f"benchmark: {cell['name']} seed={args.seed} seconds={args.seconds} trace={args.trace} on {where}")
    result = run_cell(cell, conf, bench, args.seed, args.seconds, bool(args.trace))
    summary = result.pop("summary")
    print(json.dumps({"device": result["device"], "setup": summary.pop("setup")}))
    print(json.dumps({"device": result["device"], "summary": summary}))
    for name, c in result["compared"].items():
        log(f"compared {name}: {c['value']} (limit {c['limit']}) on {where}")
    log(f"correct: {result['correct']}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
