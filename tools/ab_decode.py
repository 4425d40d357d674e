"""Same-process decode A/Bs: matmul impls, and speculative vs plain.

Separate runs differ in device state and host load, and 8B-scale runs pay
minutes of host init + weight transfer EACH — so this harness builds ONE set of
weights and runs both arms back to back in one process, interleaved
A/B/A/B to cancel slow drift.

Arms:
- ``--arm matmul`` (default): dense vs ragged block-decode matmuls through
  bench.model_throughput's wave phase (the VERDICT r4 item 2/5 numbers).
- ``--arm spec``: the async speculative pipeline (spec/decoder.py) vs the
  FUSED decode baseline through bench.spec_ab, grammar-constrained greedy
  by default. ``--draft self`` is the acceptance-1.0 / overlap-1.0 upper
  bound; named configs at random init measure the overhead floor (the
  production draft is a train/distill.py checkpoint).
- ``--arm hidden``: the draft-free hidden-transfer arm vs the same fused
  baseline — no second model; random-init heads here, train/hidden.py
  checkpoints in production.

Usage:
    python tools/ab_decode.py --model llama-3.2-1b-instruct
    python tools/ab_decode.py --model llama-3.1-8b-instruct --quantize int8
    python tools/ab_decode.py --arm spec --model llama-3.2-1b-instruct \
        --draft tiny --spec-k 4

Prints one JSON line per (impl, rep) plus a final summary line with the
throughput ratios.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama-3.2-1b-instruct")
    ap.add_argument("--quantize", default=None)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--peak-tflops", type=float, default=None)
    ap.add_argument(
        "--arm", choices=("matmul", "spec", "hidden", "fused"),
        default="matmul",
        help="matmul: dense-vs-ragged wave decode; spec: async "
             "speculative pipeline vs FUSED decode baseline; hidden: the "
             "draft-free hidden-transfer arm vs the same baseline "
             "(spec/hidden.py — no second model); fused: fused "
             "while_loop runtime vs sparse chunked decode (engine/fused/)"
             " — greedy token identity is test-pinned "
             "(tests/test_fused.py, tests/test_spec_async.py); the spec "
             "arms additionally report the round-overlap fraction and "
             "acceptance-weighted tok/s",
    )
    ap.add_argument(
        "--draft", default="tiny",
        help="spec arm: draft config name, or 'self' for the "
             "acceptance-1.0 / overlap-1.0 upper bound",
    )
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument(
        "--unconstrained", action="store_true",
        help="spec/hidden arms: drop the decision grammar (default "
             "measures grammar-constrained greedy — the serving shape)",
    )
    args = ap.parse_args()

    import jax

    from k8s_llm_scheduler_tpu.models.llama import init_params

    cfg = bench.build_cfg(args.model)

    if args.arm == "fused":
        if args.quantize == "int8":
            from k8s_llm_scheduler_tpu.models.quant import init_params_int8_host

            params = init_params_int8_host(0, cfg)
        else:
            params = init_params(jax.random.PRNGKey(0), cfg)
        # fused_ab interleaves its arms internally; reps widens the best-of
        summary = bench.fused_ab(
            args.model, quantize=args.quantize, reps=args.reps,
            n_prompts=min(args.slots, 8), params=params,
            peak_override=args.peak_tflops,
        )
        print(json.dumps(summary), flush=True)
        return
    if args.arm in ("spec", "hidden"):
        if args.quantize is not None:
            ap.error(
                f"--arm {args.arm} does not take --quantize (plain bf16 A/B)"
            )
        params = init_params(jax.random.PRNGKey(0), cfg)
        # spec_ab interleaves its arms internally; reps widens the best-of
        summary = bench.spec_ab(
            args.model, draft=args.draft, spec_k=args.spec_k,
            reps=args.reps, params=params,
            arm="hidden" if args.arm == "hidden" else "draft",
            constrained=not args.unconstrained,
        )
        print(json.dumps(summary), flush=True)
        return
    if args.quantize == "int8":
        from k8s_llm_scheduler_tpu.models.quant import init_params_int8_host

        params = init_params_int8_host(0, cfg)
    else:
        params = init_params(jax.random.PRNGKey(0), cfg)

    results: dict[str, list[dict]] = {"dense": [], "ragged": []}
    for rep in range(args.reps):
        for impl in ("dense", "ragged"):
            r = bench.model_throughput(
                args.model, args.quantize, args.peak_tflops,
                slots=args.slots, decode_matmul=impl, params=params,
            )
            r["extra"]["rep"] = rep
            results[impl].append(r)
            print(json.dumps(r), flush=True)

    def best(impl: str, key: str) -> float:
        return max(r["extra"][key] for r in results[impl])

    summary = {
        "metric": "decode_matmul_ab",
        "model": args.model,
        "quantize": args.quantize,
        "reps": args.reps,
        "decisions_per_s": {
            impl: [r["extra"]["decisions_per_s"] for r in results[impl]]
            for impl in results
        },
        "mfu_decode": {
            impl: [r["extra"].get("mfu_decode") for r in results[impl]]
            for impl in results
        },
        "wave_avg_ms": {
            impl: [r["extra"]["wave_avg_ms"] for r in results[impl]]
            for impl in results
        },
        "speedup_decisions_per_s": round(
            best("ragged", "decisions_per_s") / best("dense", "decisions_per_s"), 3
        ),
    }
    if results["dense"][0]["extra"].get("mfu_decode") is not None:
        summary["mfu_decode_ratio"] = round(
            best("ragged", "mfu_decode") / best("dense", "mfu_decode"), 3
        )
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
