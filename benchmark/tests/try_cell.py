"""One exploratory run of a cell that BENCHMARK.json does not hold yet: a
configuration file and a traffic file by name, on as many chips as given.

    python3 benchmark/tests/try_cell.py internlm2_5-20b backlog 4 20 <seed>

Prints the set-up breakdown, the end-to-end numbers, waves and seconds a
wave, and the peak memory. It makes no comparison with the reference (a
configuration that is new may not have limits yet, or a reference that fits):
what it prints says what a cell would cost, not that it is correct.
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

    config, traffic, chips, seconds, seed = sys.argv[1:6]
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    conf = seam.load_config(BENCH / "configs" / f"{config}.json")
    cell = {"name": f"try-{config}-{traffic}", "config": config, "traffic": traffic, "chips": int(chips)}
    r = bench_run.run_cell(cell, conf, bench, seed=int(seed), seconds=float(seconds), trace=False,
                           reference=False)
    s = r["summary"]
    waves = s["waves"]
    print(json.dumps({
        "cell": cell, "device": r["device"], "setup": s["setup"], "end_to_end": s["end_to_end"],
        "attempted": r["attempted"], "failed": r["failed"], "decisions": s["decisions"],
        "waves": waves, "seconds_a_wave": float(seconds) / waves if waves else None,
        "engine": s["engine"], "window_compiles": s["window_compiles"],
        "window_compiled_names": s["window_compiled_names"],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
