"""The sweep that fixes a timetable cell's period, on the chip, once:

    python3 benchmark/tests/sweep_period.py internlm1_8b-bursts 45 2.0 2.4 2.8 3.2

For each period: one run of the cell with that period, printing for the
largest burst size the median time from due to last bind, as a share of the
period (the rule: the shortest period at which that share is <= 0.8), and
the latencies. One process; each run builds and frees its own stack.
"""

from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def main() -> int:
    import run as bench_run
    from harness import traffic as T

    workload, seconds = sys.argv[1], float(sys.argv[2])
    bench, cell, entry = bench_run.load_cell(workload)
    conf = json.loads((BENCH.parent / entry["file"]).read_text())
    for i, period in enumerate(float(p) for p in sys.argv[3:]):
        mix = T.load_traffic(cell["traffic"])
        mix["period_s"] = period
        r = bench_run.run_cell(cell, conf, bench, seed=9000 + i, seconds=seconds, trace=False,
                               mix_override=mix)
        bursts = r["summary"]["bursts"]
        big = max(n for n, _ in bursts)
        lasts = [t for n, t in bursts if n == big and t is not None]
        print(json.dumps({
            "period_s": period, "device": r["device"]["kind"], "bursts": len(bursts),
            "largest_burst_last_bind_s_median": statistics.median(lasts) if lasts else None,
            "share_of_period": statistics.median(lasts) / period if lasts else None,
            "unfinished_largest": sum(1 for n, t in bursts if n == big and t is None),
            "per_size_median_last_bind_s": {
                n: statistics.median([t for m, t in bursts if m == n and t is not None] or [float("nan")])
                for n in sorted({n for n, _ in bursts})},
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "correct": r["correct"], "failed": r["failed"], "attempted": r["attempted"],
            "waves": r["summary"]["waves"], "engine": r["summary"]["engine"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
