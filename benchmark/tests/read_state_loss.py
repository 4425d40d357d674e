"""How far the comparison that decides `correct` moves when a pinned prefix
loses its per-sequence state, on the chip at the cell's own size: one short
run of the cell as it is, and one in which every prefix entry the engine
makes keeps its cache and a ZEROED state (what a pin that held (k, v) alone
would serve: every wave's rows start the delta rule from nothing).

    python3 benchmark/tests/read_state_loss.py qwen3_next-backlog20 15 <seed>

Prints one JSON line a run: the program's `mean_gap` and `worst_gap` under
the cell's limits, sound and with the state lost. A reading, reported in
PERF.md, not a limit: it says whether a lost or stale state is a fault the
cell's `correct` can see (tests/test_gdn_moe.py holds the same at a toy size
in float32). The loss is planted from this side, on the engine's own prefix
entry type; no file of the program or of the harness is edited.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def lose_state() -> None:
    """Every `_PrefixKV` made from here on holds zeros for its state."""
    import dataclasses

    import jax.numpy as jnp

    from k8s_llm_scheduler_tpu.engine import engine

    sound = engine._PrefixKV

    @dataclasses.dataclass
    class Lost(sound):
        def __post_init__(self):
            self.state = tuple(jnp.zeros_like(a) for a in self.state)

    engine._PrefixKV = Lost


def main() -> int:
    import run as bench_run
    from harness import seam

    workload, seconds, seed = sys.argv[1], float(sys.argv[2]), int(sys.argv[3])
    bench, cell, entry = bench_run.load_cell(workload)
    conf = seam.load_config(BENCH.parent / entry["file"])
    for state in ("sound", "lost"):
        if state == "lost":
            lose_state()
        r = bench_run.run_cell(cell, conf, bench, seed=seed, seconds=seconds, trace=False)
        print(json.dumps({
            "seed": seed, "device": r["device"]["kind"], "state": state,
            "program": r["summary"]["program_gaps"], "correct": r["correct"],
            "compared": r["compared"], "sampled": r["summary"]["sampled"], "failed": r["failed"],
            "binds_per_s": r["summary"]["end_to_end"].get("binds_per_s"),
            "memory_peak_bytes": r["device"]["memory_peak_bytes"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
