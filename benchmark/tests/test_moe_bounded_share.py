"""`moe_bounded_share.tput`: what it reads from a window whose program
counts `moe_bounded_calls` beside `moe_layer_calls` (models/mla_moe.py
BOUND_COUNTERS), and from a program that does not (a parent of the PR that
brought the counter: None, never 0%, and nothing raised).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_moe_bounded_share.py -q
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from test_counter_readers import BENCH, window_ctx  # noqa: E402

# 100 waves of ~21 model calls over 4 routed layers; the window opens on a
# program that has run its warm-up waves
BEFORE = {"waves": 12, "moe_layer_calls": 1_008, "moe_bounded_calls": 1_008}
AFTER = {"waves": 112, "moe_layer_calls": 9_408, "moe_bounded_calls": 9_408}


@pytest.mark.parametrize("before, after, want", [
    (BEFORE, AFTER, 100.0),
    (BEFORE, {**AFTER, "moe_bounded_calls": 9_408 - 21}, 100.0 * (8_400 - 21) / 8_400),  # 21 calls overflowed
    (BEFORE, {**AFTER, "moe_bounded_calls": 1_008}, 0.0),                                # every call did
    ({k: v for k, v in BEFORE.items() if k != "moe_bounded_calls"},
     {k: v for k, v in AFTER.items() if k != "moe_bounded_calls"}, None),                # a parent
    ({"waves": 12}, {"waves": 112}, None),                                               # another architecture
    (AFTER, AFTER, None),                                                                # no wave in the window
])
def test_bounded_calls_over_layer_calls(before, after, want):
    import run as bench_run

    got = bench_run.reader_for("moe_bounded_share.tput")(window_ctx(before, after))
    assert got == pytest.approx(want) if want is not None else got is None


def test_the_entry_is_the_third_cells_alone():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = bench["per_layer"][-1]
    assert entry == {"name": "moe_bounded_share.tput", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "model", "moves": "binds_per_s",
                     "workloads": ["longcat_flash-backlog20"]}
