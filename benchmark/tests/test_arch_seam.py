"""The architecture seam (harness/seam.py, arch/README.md): a configuration
of another architecture comes in by files alone.

The proof is a second architecture that only this test holds:
`dense_gqa_twin`, an arch file and a reference file written into a
temporary directory, which wrap the dense ones, count their calls, and
count every FLOP double. A whole run of the rehearsal's backlog mix at the
toy size then goes through them and comes out correct; no committed file is
touched. The other tests take under a second each: nothing under
harness/, metrics/ or run.py names an architecture, and the FLOP counts of
`internlm2_5-1_8b` are the parent's to the last digit.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_arch_seam.py -q
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

TWIN = "dense_gqa_twin"
TWIN_ARCH = '''
import collections
import importlib.util

CALLS = collections.Counter()
_spec = importlib.util.spec_from_file_location("twin_dense_arch", {dense!r})
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)


def register(conf):
    CALLS["register"] += 1
    return _dense.register(conf)


def flops_per_token(conf, with_head):
    CALLS["flops_per_token"] += 1
    return 2 * _dense.flops_per_token(conf, with_head)


def attention_flops(conf, queries, keys_per_query):
    CALLS["attention_flops"] += 1
    return 2 * _dense.attention_flops(conf, queries, keys_per_query)
'''
TWIN_REFERENCE = '''
import collections
import importlib.util

CALLS = collections.Counter()
_spec = importlib.util.spec_from_file_location("twin_dense_reference", {dense!r})
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)


def init_weights(conf, seed):
    CALLS["init_weights"] += 1
    return _dense.init_weights(conf, seed)


def wave_logits(conf, weights, prefix_ids, tails, pred_spans, mode, vocab_rows):
    CALLS["wave_logits:" + mode] += 1
    return _dense.wave_logits(conf, weights, prefix_ids, tails, pred_spans, mode, vocab_rows)
'''


@pytest.fixture
def twin(tmp_path, monkeypatch):
    """The seam pointed at a directory that holds the twin and nothing
    else, with nothing loaded yet."""
    from harness import seam

    for directory, text in (("arch", TWIN_ARCH), ("reference", TWIN_REFERENCE)):
        (tmp_path / directory).mkdir()
        (tmp_path / directory / f"{TWIN}.py").write_text(
            text.format(dense=str(BENCH / directory / "dense_gqa.py")))
    monkeypatch.setattr(seam, "ROOT", tmp_path)
    monkeypatch.setattr(seam, "_loaded", {})
    return seam


def _mfu_ctx(conf):
    """One written-out window for metrics/model_mfu.py: three waves of
    eight rows behind a 1,500-token prefix, one prefix prefill."""
    waves = [{"prompts": [[0] * (60 + r) for r in range(8)], "served": [[0] * 42] * 8,
              "prefix_ids": [0] * 1500} for _ in range(3)]
    suffix_tokens = sum(len(p) for w in waves for p in w["prompts"])
    return SimpleNamespace(
        conf=conf, waves=waves, seconds=10.0, chips=1, peaks={"bf16_flops": 197e12},
        outcome=SimpleNamespace(t0=0.0, t1=10.0), prefix_prefills=[(1.0, 1.1, 1500)],
        delta=lambda *path: suffix_tokens + 1500)


def test_a_twin_architecture_runs_through_the_seam_by_files_alone(twin, monkeypatch):
    import rehearse
    import run as bench_run
    from harness import traffic as T

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    mix = rehearse.small(T.load_traffic("backlog"))
    conf = {**rehearse.TOY, "architecture": TWIN}
    cell = {"name": "test-twin-backlog", "config": conf["name"], "traffic": "backlog", "chips": 1}
    result = bench_run.run_cell(cell, conf, bench, seed=78, seconds=10.0, trace=False, mix_override=mix)
    assert result["correct"], result["compared"]
    arch, reference = twin.program(conf), twin.reference(conf)
    assert arch.CALLS["register"] == 1
    assert reference.CALLS["init_weights"] == 1
    assert reference.CALLS["wave_logits:f32"] >= 1 and reference.CALLS["wave_logits:int8"] == 0
    # the harness asked the seam for the twin's two files and for no other
    assert sorted(twin._loaded) == [twin.ROOT / "arch" / f"{TWIN}.py", twin.ROOT / "reference" / f"{TWIN}.py"]

    # its own FLOPs in model_mfu: twice the dense count, for the same waves
    read = bench_run.reader_for("model_mfu.tput")
    calls = arch.CALLS["flops_per_token"]
    doubled = read(_mfu_ctx(conf))
    assert arch.CALLS["flops_per_token"] > calls
    monkeypatch.setattr(twin, "ROOT", BENCH)
    assert doubled == pytest.approx(2.0 * read(_mfu_ctx(dict(rehearse.TOY))), rel=1e-12)


def test_a_configuration_without_the_key_is_an_error_that_names_it(tmp_path):
    from harness import seam

    import rehearse

    conf = {k: v for k, v in rehearse.TOY.items() if k != seam.KEY}
    path = tmp_path / "nameless.json"
    path.write_text(json.dumps(conf))
    with pytest.raises(SystemExit) as stop:
        seam.load_config(path)
    assert "nameless.json" in str(stop.value) and '"architecture"' in str(stop.value)
    with pytest.raises(KeyError, match="rehearsal-toy.*architecture"):
        seam.program(conf)
    with pytest.raises(FileNotFoundError, match="no_such_arch"):
        seam.reference({**conf, seam.KEY: "no_such_arch"})
    # every committed configuration says what it is, and both its files are there
    for file in sorted((BENCH / "configs").glob("*.json")):
        committed = seam.load_config(file)
        assert (BENCH / "arch" / f"{committed[seam.KEY]}.py").exists(), file
        assert (BENCH / "reference" / f"{committed[seam.KEY]}.py").exists(), file


def test_harness_metrics_and_run_name_no_architecture():
    files = sorted((BENCH / "harness").glob("*.py")) + sorted((BENCH / "metrics").glob("*.py")) + [BENCH / "run.py"]
    assert len(files) > 30
    named = [f"{f.relative_to(BENCH)}:{i}: {line.strip()}" for f in files
             for i, line in enumerate(f.read_text().splitlines(), 1)
             if re.search(r"dense_gqa|LlamaConfig", line)]
    assert named == []


def test_flop_counts_of_internlm2_5_1_8b_are_the_parents_to_the_last_digit():
    """Constants computed with the parent's harness/flops.py (commit
    504943a: `dense_flops_per_token`, `wave_flops`, `prefix_prefill_flops`)."""
    from harness import flops, seam

    conf = seam.load_config(BENCH / "configs" / "internlm2_5-1_8b.json")
    arch = seam.program(conf)
    assert arch.flops_per_token(conf, with_head=False) == 3_019_898_880
    assert arch.flops_per_token(conf, with_head=True) == 3_398_959_104
    # eight suffixes of 67 tokens, 42 served tokens each, behind a prefix of 1,500
    assert flops.wave_flops(conf, [67] * 8, [42] * 8, 1500) == 3030341124096.0
    # a whole 1,500-token prefix; 200 new tokens at the end of 1,700
    assert flops.prefix_prefill_flops(conf, 1500, 1500) == 4751179776000.0
    assert flops.prefix_prefill_flops(conf, 200, 1700) == 666913996800.0
