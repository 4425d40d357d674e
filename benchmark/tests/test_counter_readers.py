"""Readers that divide one window delta of the program's counters by
another: what they read from a window that has the counter, and from a
program that lacks it (a parent of the PR that brought the counter: the
delta reads 0, the reader returns None and raises nothing).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_counter_readers.py -q
"""

from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def window_ctx(before: dict, after: dict):
    """A reader's context over two snapshots of `engine.stats`."""
    import run as bench_run

    before, after = ({"sched": {"client": {"engine": stats}}} for stats in (before, after))
    return bench_run.Ctx(outcome=SimpleNamespace(before=before, after=after))


# 20 full waves of 8 submitted in the window, 67-token suffixes, one
# 1,500-token prefix prefill; 5 waves more finished in it than it submitted
WINDOW = ({"requests": 80, "completed": 40, "prefill_tokens": 9_000, "suffix_tokens_computed": 10_240},
          {"requests": 240, "completed": 240, "prefill_tokens": 9_000 + 160 * 67 + 1_500,
           "suffix_tokens_computed": 10_240 + 20 * 8 * 128})


@pytest.mark.parametrize("name, drop, want", [
    ("suffix_tok_computed_per_dec.tput", (), 128.0),
    ("suffix_tok_computed_per_dec.tput", ("suffix_tokens_computed",), None),  # a parent
    ("suffix_tok_computed_per_dec.tput", ("requests",), None),                # nothing submitted
    ("prefill_tok_per_dec.tput", (), (160 * 67 + 1_500) / 200),
    ("prefill_tok_per_dec.tput", ("completed",), None),
])
def test_a_counter_over_decisions(name, drop, want):
    import run as bench_run

    before, after = ({k: v for k, v in side.items() if k not in drop} for side in WINDOW)
    assert bench_run.reader_for(name)(window_ctx(before, after)) == want


def test_the_entry_beside_the_real_tokens():
    entries = {m["name"]: m for m in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["per_layer"]}
    new, real = entries["suffix_tok_computed_per_dec.tput"], entries["prefill_tok_per_dec.tput"]
    assert {k: new[k] for k in ("unit", "better", "source", "layer", "moves")} == {
        "unit": "tokens", "better": "lower", "source": "program_counter",
        "layer": "engine", "moves": "binds_per_s"}
    assert new["workloads"] == real["workloads"]
