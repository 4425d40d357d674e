"""The five readers the delta-rule family brought (PR 37): the four that
read device time by scope, on a synthetic reduction of a traced slice with
the family's scope paths, and `state_valid_share.tput` on window deltas of
the wave's counters; each on a program that has nothing for it to read (a
parent, another architecture: None, never 0, and nothing raised).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_state_readers.py -q
"""

from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from test_counter_readers import BENCH, window_ctx  # noqa: E402

CELL = "qwen3_next-backlog20"
BINDS = 240
# seconds by scope path, as metrics/_scope_trace.py reduces a slice: the
# paths models/gdn_moe.py gives its operations in the three programs
SCOPES = {
    "block_decode/model/attn/gdn/gdn_proj": 0.60, "block_decode/model/attn/gdn/gdn_conv": 0.05,
    "block_decode/model/attn/gdn/gdn_scan": 0.90, "block_decode/model/attn/gdn/gdn_out": 0.25,
    "block_decode/model/attn/gdn/state_writeback": 0.012, "block_decode/model/attn/gdn": 0.02,
    "block_decode/model/attn/full_attn": 0.40, "block_decode/model/attn": 0.03,
    "suffix_prefill/attn/gdn/gdn_scan": 0.06, "suffix_prefill/attn/gdn/gdn_proj": 0.04,
    "suffix_prefill/attn/gdn/state_writeback": 0.001, "suffix_prefill/state_seed": 0.011,
    "suffix_prefill/attn/full_attn": 0.05, "prefix_prefill/attn/gdn/gdn_scan": 0.08,
    "prefix_prefill/attn/full_attn": 0.07, "block_decode/model/mlp/moe_shared": 0.3,
    "block_decode/model/kv_writeback": 0.02, "(no scope)": 0.01,
}
WANT = {
    "gdn_device_ms_per_bind.tput": 0.60 + 0.05 + 0.90 + 0.25 + 0.012 + 0.02 + 0.06 + 0.04 + 0.001 + 0.08,
    "gdn_scan_device_ms_per_bind.tput": 0.90 + 0.06 + 0.08,
    "full_attn_device_ms_per_bind.tput": 0.40 + 0.05 + 0.07,
    "state_carry_device_ms_per_bind.tput": 0.011 + 0.012 + 0.001,
}


def scoped_ctx(scopes: dict | None):
    """A context whose slice is already reduced (`_scope_trace.reduced`
    keeps its result on the context): readers see seconds by scope path."""
    import run as bench_run

    reduced = None if scopes is None else {"busy_s": sum(scopes.values()), "scopes": scopes, "binds": BINDS}
    return bench_run.Ctx(_scope_trace=reduced, outcome=SimpleNamespace(trace_span=(0.0, 6.0)))


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_scope_reader_sums_its_scope_in_every_program(name):
    import run as bench_run

    read = bench_run.reader_for(name)
    assert read(scoped_ctx(SCOPES)) == pytest.approx(1000.0 * WANT[name] / BINDS, rel=1e-12)
    # another family's slice has no such scope; a program that names nothing has no reduction
    other = {k: v for k, v in SCOPES.items() if "gdn" not in k and "full_attn" not in k and "state_" not in k}
    assert read(scoped_ctx(other)) is None
    assert read(scoped_ctx(None)) is None


def test_state_carry_reads_one_scope_where_only_one_ran():
    import run as bench_run

    read = bench_run.reader_for("state_carry_device_ms_per_bind.tput")
    only = {"suffix_prefill/state_seed": 0.024, "block_decode/model/mlp": 1.0}
    assert read(scoped_ctx(only)) == pytest.approx(1000.0 * 0.024 / BINDS)


# 100 waves: 8 rows x 128 a suffix call and 8 x 24 in each of 21 decode calls
# computed, 563 suffix tokens and 8 x 42 served tokens valid
BEFORE = {"waves": 12, "state_tokens_valid": 12 * 899, "state_tokens_computed": 12 * 5_056}
AFTER = {"waves": 112, "state_tokens_valid": 112 * 899, "state_tokens_computed": 112 * 5_056}


@pytest.mark.parametrize("before, after, want", [
    (BEFORE, AFTER, 100.0 * 899 / 5_056),
    ({"waves": 12}, {"waves": 112}, None),   # a parent, another architecture: no such counters
    (AFTER, AFTER, None),                    # no wave in the window
])
def test_valid_positions_over_computed(before, after, want):
    import run as bench_run

    got = bench_run.reader_for("state_valid_share.tput")(window_ctx(before, after))
    assert got == pytest.approx(want) if want is not None else got is None


def test_the_five_entries_close_the_list_and_name_the_cell_alone():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entries = bench["per_layer"][-5:]
    assert [m["name"] for m in entries] == [
        "gdn_device_ms_per_bind.tput", "gdn_scan_device_ms_per_bind.tput", "full_attn_device_ms_per_bind.tput",
        "state_carry_device_ms_per_bind.tput", "state_valid_share.tput"]
    for m in entries:
        assert m["workloads"] == [CELL] and m["moves"] == "binds_per_s" and m["layer"] == "model"
    assert [m["source"] for m in entries] == ["device_trace"] * 4 + ["program_counter"]
    assert entries[-1]["unit"] == "%" and entries[-1]["better"] == "higher"
