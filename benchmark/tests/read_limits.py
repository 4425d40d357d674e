"""Readings for the limits of the comparison that decides `correct`, on the
chip at the cell's own size: for each seed one short run of the cell, the
program's worst gap and, from the same sampled waves, the int8 control's.

    python3 benchmark/tests/read_limits.py internlm1_8b-backlog 12 101 102 103 104
    JAX_PLATFORMS=cpu python3 benchmark/tests/read_limits.py toy-backlog 10 1 2 3

(`toy-<traffic>`: the rehearsal's toy model on an 8-node cluster, the size
tests/test_faults.py runs at; its limits are a file of their own,
limits/rehearsal-toy.json, read the same way.)
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def main() -> int:
    import run as bench_run
    from harness import seam

    workload, seconds = sys.argv[1], float(sys.argv[2])
    mix = None
    if workload.startswith("toy-"):  # the tests' size, on the CPU
        import rehearse
        from harness import traffic as T

        bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
        traffic = workload.split("-", 1)[1]
        cell = {"name": workload, "config": "rehearsal-toy", "traffic": traffic, "chips": 1}
        conf, mix = dict(rehearse.TOY), rehearse.small(T.load_traffic(traffic))
    else:
        bench, cell, entry = bench_run.load_cell(workload)
        conf = seam.load_config(BENCH.parent / entry["file"])
    fault = None
    seeds = sys.argv[3:]
    if seeds and seeds[0].startswith("fault="):
        fault, seeds = seeds[0].split("=", 1)[1], seeds[1:]
    dump = None  # dump=<dir>: every choice's gap, program's and control's, for a look offline
    if seeds and seeds[-1].startswith("dump="):
        dump, seeds = Path(seeds[-1].split("=", 1)[1]), seeds[:-1]
    for seed in (int(s) for s in seeds):
        r = bench_run.run_cell(cell, conf, bench, seed=seed, seconds=seconds, trace=False,
                               control=fault is None, fault=fault, mix_override=mix)
        lists = r["summary"].pop("gap_lists", None)
        if dump is not None and lists is not None:
            dump.mkdir(parents=True, exist_ok=True)
            (dump / f"gaps-{workload}-{seed}.json").write_text(json.dumps(lists))
        print(json.dumps({
            "seed": seed, "device": r["device"]["kind"], "fault": fault,
            "program": r["summary"]["program_gaps"],
            "control": r["summary"].get("control_gaps"),
            "judged_is": "int8 control" if fault is None else f"program with fault {fault}",
            "correct_of_judged": r["correct"], "compared": r["compared"],
            "sampled": r["summary"]["sampled"], "failed": r["failed"],
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "engine": r["summary"]["engine"], "waves": r["summary"]["waves"],
            "window_compiles": r["summary"]["window_compiles"],
            "memory_peak_bytes": r["device"]["memory_peak_bytes"],
            "reference_s": r["summary"]["reference_s"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
