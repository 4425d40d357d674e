"""Rehearsal without the chip: every traffic kind at a toy size on the CPU.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [--tp 4]

Checks control flow end to end (build, warm-up, window, reference), that two
seeds give identical class counts, arrival counts, burst sizes and
suffix-length histograms, and the shape of the result object. It prints
counts and shapes only: nothing measured here is a device number, and none
is printed under a device metric's name. `--tp 4` runs the same on four
virtual CPU devices (XLA_FLAGS=--xla_force_host_platform_device_count=4).
"""

from __future__ import annotations

import argparse
import collections
import copy
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

TOY = {
    "name": "rehearsal-toy", "architecture": "dense_gqa", "hidden_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "intermediate_size": 512, "vocab_size": 1280, "rope_theta": 10000.0,
    "max_position_embeddings": 8192, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "bias": False, "hidden_act": "silu", "torch_dtype": "bfloat16",
    "serve": {"llm.temperature": 0.0}, "weights_seed": 0,
}


def small(mix: dict) -> dict:
    """The traffic file as committed, on an 8-node cluster."""
    mix = copy.deepcopy(mix)
    spec = mix["cluster_spec"]
    spec["nodes"] = 8
    for cls, n in zip(spec["capacity_classes"], (3, 3, 1, 1)):
        cls["count"] = n
    for cls, n in zip(spec["pools"], (6, 1, 1)):
        cls["count"] = n
    spec["preloaded_pods_range"] = [0, 4]
    # one GPU node and one batch node: roomy enough for their pods on every seed
    spec["usage_percent_range"] = [5.0, 30.0]
    spec["drift"]["nodes_per_tick"] = 1
    mix["pool_blocks"] = min(mix.get("pool_blocks", 1), 64)
    if mix["kind"] == "timetable":
        mix["period_s"] = 1.0
    return mix


def stratification(mix: dict, seconds: float) -> dict:
    """Facts of the generated traffic that must not depend on the seed."""
    from transformers import AutoTokenizer

    from harness import traffic as T
    from harness.system import raw_pod
    from k8s_llm_scheduler_tpu.cluster.interface import raw_pod_to_spec
    from k8s_llm_scheduler_tpu.core.prompt import pod_suffix
    from k8s_llm_scheduler_tpu.testing import BPE_FIXTURE

    tok = AutoTokenizer.from_pretrained(BPE_FIXTURE, local_files_only=True)
    facts = []
    for seed in (11, 2**31 + 7):
        if mix["kind"] == "closed_depth":
            pods, bursts = T.closed_depth_pods(mix, seed), None
        else:
            bursts = T.timetable(mix, seed, seconds)[1]
            pods = [p for b in bursts for p in b.pods]
        hist = collections.Counter(
            len(tok.encode(pod_suffix(raw_pod_to_spec(raw_pod(p))), add_special_tokens=False))
            for p in pods)
        facts.append({
            "arrivals": len(pods), "classes": T.class_counts(pods),
            "burst_sizes": sorted(len(b.pods) for b in bursts) if bursts else None,
            "suffix_hist": dict(sorted(hist.items())),
            "node_capacities": sorted(collections.Counter(
                n.cpu_cores for n in T.make_nodes(mix["cluster_spec"], seed)).items()),
            "node_pools": sorted(collections.Counter(
                (tuple(sorted(n.labels)), len(n.taints))
                for n in T.make_nodes(mix["cluster_spec"], seed)).items()),
        })
    if facts[0] != facts[1]:
        raise AssertionError(f"traffic depends on the seed:\n{facts[0]}\n{facts[1]}")
    return facts[0]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args()
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        raise SystemExit("rehearse.py is for JAX_PLATFORMS=cpu; the chip runs benchmark/run.py")
    if args.tp > 1:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_force_host_platform_device_count={args.tp}")
    import run as bench_run
    from harness import traffic as T

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    conf = dict(TOY)
    if args.tp > 1:  # heads and KV heads a multiple of tp
        conf.update(hidden_size=64 * 2 * args.tp, num_attention_heads=2 * args.tp,
                    num_key_value_heads=args.tp)
    conf["serve"] = {**conf["serve"], "llm.mesh": {"dp": 1, "tp": args.tp}}
    ok = True
    for traffic in sorted({w["traffic"] for w in bench["workloads"]} | {"bursts", "replicas"}):  # every kind
        full = T.load_traffic(traffic)
        facts_full = stratification(full, bench["run_seconds"])
        mix = small(full)
        cell = {"name": f"rehearsal-{traffic}", "config": conf["name"], "traffic": traffic, "chips": args.tp}
        result = bench_run.run_cell(cell, conf, bench, seed=2**31 + 5, seconds=args.seconds,
                                    trace=False, mix_override=mix)
        keys = [k for k in ("correct", "attempted", "failed", "metrics", "device") if k in result]
        shape_ok = keys == ["correct", "attempted", "failed", "metrics", "device"] and list(result)[-1] == "compared"
        # a CPU misses a timetable's deadlines; what must hold here is the
        # result's shape and the comparison with the reference
        agrees = all(c["value"] <= c["limit"] for k, c in result["compared"].items()
                     if k != "failed_operations")
        ok &= shape_ok and agrees
        print(json.dumps({
            "rehearsal": traffic, "platform": result["device"]["platform"], "tp": args.tp,
            "result_keys": list(result), "shape_ok": shape_ok, "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "reported": sorted(result["metrics"]), "compared": result["compared"],
            "decisions": result["summary"]["decisions"], "waves": result["summary"]["waves"],
            "window_compiles": result["summary"]["window_compiles"],
            "arrivals_full_size": facts_full["arrivals"], "classes_full_size": facts_full["classes"],
            "suffix_hist_full_size": facts_full["suffix_hist"],
        }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
