"""The trace reduction reproduces known numbers from a small recorded trace
(tests/data/small_trace.xplane.pb, taken on a TPU v5 lite by
tests/record_fixture.py: three host spans, four 2048^2 bf16 matmul steps
in each, pauses between them).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_xplane.py -q
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
DATA = Path(__file__).resolve().parent / "data"


def test_reduction_reproduces_recorded_numbers():
    from harness import xplane

    got = xplane.reduce(xplane.load(str(DATA / "small_trace.xplane.pb")))
    want = json.loads((DATA / "small_trace.expected.json").read_text())
    assert got["devices"] == want["devices"]
    for key in ("window_s", "busy_s", "collective_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9, abs=1e-12)
    assert [n for n, _ in got["device_ops"]] == [n for n, _ in want["device_ops"]]
    assert dict(map(tuple, got["idle_gaps"])) == pytest.approx(dict(map(tuple, want["idle_gaps"])))
    # what the fixture was built to show: the device idles most of the
    # time, and the gaps fall under the spans that slept
    assert 0 < got["busy_s"] < 0.5 * got["window_s"]
    assert {"submit_wave", "harvest_wave", "bind"} <= {n for n, _ in got["idle_gaps"]}


def test_flop_and_byte_functions():
    from harness import flops, seam

    conf = seam.load_config(BENCH / "configs" / "internlm2_5-1_8b.json")
    # 2 FLOPs per weight per token, embedding row lookup excluded
    weights = conf["parameters"] - conf["vocab_size"] * conf["hidden_size"] - (
        2 * conf["num_hidden_layers"] + 1) * conf["hidden_size"]
    assert seam.program(conf).flops_per_token(conf, with_head=True) == 2 * weights
    f, b = flops.prefix_kernel_cost(8, 192, 128, 8000)
    assert f == 4 * 8 * 192 * 8000 * 128
    assert b == 2 * 8 * 8000 * 128 * 2 + 8 * 192 * (2 * 128 + 256) * 4
