"""The rest of a run with the timed path broken underneath: `correct` must
come out false. Drives run.run_cell (which does not look for a chip) at the
rehearsal's toy size on the CPU, once sound and once for each fault a served
one-chip cell can have: a token altered where it is produced. (A step that
returns its state unchanged and half a batch left out are training faults;
the exchange between chips exists only in a tp>1 cell.)

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_faults.py -q
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def _run(fault=None, control=False):
    import rehearse
    import run as bench_run
    from harness import traffic as T

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    mix = rehearse.small(T.load_traffic("backlog"))
    cell = {"name": "test-backlog", "config": "rehearsal-toy", "traffic": "backlog", "chips": 1}
    return bench_run.run_cell(cell, dict(rehearse.TOY), bench, seed=77, seconds=10.0, trace=False,
                              mix_override=mix, fault=fault, control=control)


def test_sound_run_is_correct():
    result = _run()
    assert result["correct"], result["compared"]


@pytest.mark.parametrize("fault", ["altered_token"])
def test_fault_comes_out_not_correct(fault):
    result = _run(fault=fault)
    assert not result["correct"], result["compared"]
    assert result["compared"]["worst_gap"]["value"] > result["compared"]["worst_gap"]["limit"]


def test_control_in_int8_comes_out_not_correct():
    """The control: the reference in the program's place, computed with int8
    weights and bfloat16 arithmetic (the nearest precision under the
    configuration's bfloat16). Judged at each choice is the token the int8
    forward puts first."""
    result = _run(control=True)
    assert not result["correct"], result["compared"]
