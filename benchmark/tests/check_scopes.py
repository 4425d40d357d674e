"""On the chip: one traced run of a cell with the capture kept, then the
per-scope readers (metrics/_scope_trace.py) against the program's own
reduction of the same file (`tools/trace_scopes.py`,
observability/scopes.py `reduce_scopes`).

    python3 benchmark/tests/check_scopes.py internlm1_8b-backlog20 <seed> 51 chiprun_out/scopes.json

Prints, and writes to the file named, the run's result line, each scope
metric beside what the tool reads for the same last path component (they
have to agree: the same two passes over the same events), every scope
path's milliseconds a bind, and whether all the scopes together account for
the device block's `busy_s` (which spans the wrappers too, so it reads a
little over the sum of the operations).
"""

from __future__ import annotations

import contextlib
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

SCOPE_OF = {"mlp_device_ms_per_bind.tput": "mlp", "attn_device_ms_per_bind.tput": "attn",
            "kv_writeback_device_ms_per_bind.tput": "kv_writeback", "lm_head_device_ms_per_bind.tput": "lm_head",
            "layer_loop_device_ms_per_bind.tput": "model"}


def main() -> int:
    import run as bench_run
    from harness import seam

    workload, seed, seconds, out_file = sys.argv[1], int(sys.argv[2]), float(sys.argv[3]), Path(sys.argv[4])
    kept = BENCH.parent / ".bench_out" / "kept.xplane.pb"
    seen: dict = {"reader_s": {}}
    capture, reader_for = bench_run.Tracer.capture, bench_run.reader_for

    @contextlib.contextmanager
    def keeping(self):
        with capture(self) as (path, profile):
            kept.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(path, kept)
            yield path, profile

    def timed_reader_for(name):
        read = reader_for(name)

        def timed(ctx):
            seen["ctx"] = ctx
            t0 = time.perf_counter()
            try:
                return read(ctx)
            finally:
                seen["reader_s"][name] = time.perf_counter() - t0

        return timed

    bench_run.Tracer.capture, bench_run.reader_for = keeping, timed_reader_for
    bench, cell, entry = bench_run.load_cell(workload)
    conf = seam.load_config(BENCH.parent / entry["file"])
    result = bench_run.run_cell(cell, conf, bench, seed, seconds, trace=True)
    result.pop("summary")

    from k8s_llm_scheduler_tpu.observability.scopes import reduce_scopes

    tool = reduce_scopes(str(kept))
    mine = seen["ctx"]._scope_trace
    binds = mine["binds"]
    values = {k: v["value"] for k, v in result["metrics"].items()}
    by_path: dict[str, float] = {}
    for per in tool["scopes"].values():
        for path, s in per.items():
            by_path[path] = by_path.get(path, 0.0) + s
    rows = {}
    for name, scope in SCOPE_OF.items():
        own = scope == "model"  # the layer loop is what `model` does itself; the others, all they hold
        theirs = 1000.0 * sum(s for p, s in by_path.items()
                              if (p.rsplit("/", 1)[-1] == scope if own else scope in p.split("/"))) / binds
        rows[name] = {"reader": values.get(name), "tool": theirs}
    rows["unscoped_device_share.tput"] = {"reader": values.get("unscoped_device_share.tput"),
                                          "tool": 100.0 * tool["no_scope"]["share"]}
    for row in rows.values():
        row["rel_diff"] = row["reader"] / row["tool"] - 1.0 if row["reader"] and row["tool"] else None
    all_scopes_ms = 1000.0 * sum(mine["scopes"].values()) / binds
    busy_ms = 1000.0 * result["device"]["busy_s"] / binds
    named = sum(values.get(n) or 0.0 for n in SCOPE_OF)
    unscoped_ms = 1000.0 * mine["scopes"].get("(no scope)", 0.0) / binds
    report = {
        "result": result, "binds": binds, "tool_measured": tool["measured"], "rows": rows,
        "five_named_ms_per_bind": named, "unscoped_ms_per_bind": unscoped_ms,
        "other_scopes_ms_per_bind": all_scopes_ms - named - unscoped_ms,
        "all_scopes_ms_per_bind": all_scopes_ms, "busy_ms_per_bind": busy_ms,
        "all_scopes_over_busy": all_scopes_ms / busy_ms - 1.0,
        "ms_per_bind_by_path": {p: 1000.0 * s / binds for p, s in sorted(by_path.items(), key=lambda kv: -kv[1])},
        "tool_busy_s": tool["busy_s"], "reader_busy_s": mine["busy_s"],
        "block_decode": tool["block_decode"], "reader_seconds": seen["reader_s"],
        "trace_bytes": kept.stat().st_size,
    }
    kept.unlink()
    out_file.parent.mkdir(parents=True, exist_ok=True)
    out_file.write_text(json.dumps(report, indent=1))
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
