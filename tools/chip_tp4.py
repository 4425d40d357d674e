"""Builder-run, four-chip host: the chip_smoke body under `llm.mesh: {tp: 4}`.

    python tools/chip_tp4.py       # needs 4 TPU chips; exits 1 if a check fails

One process, three greedy (temperature 0) runs of chip_smoke.run():

1. llama-3.2-1b-instruct at tp=1  — the digest to match
2. llama-3.2-1b-instruct at tp=4  — same prompts: token digest equal to (1),
   params / paged KV / prefix each a NamedSharding over four devices,
   per-device bytes_in_use within 1.5x of one another
3. llama-3.1-8b-instruct bf16 at tp=4 — 16 GB of weights, the size that
   NEEDS the mesh: starts and serves the same pods

A run that dies is recorded with where it stopped, and the later runs still
go. The full record goes to chiprun_out/chip_tp4.json; the last stdout line
is the verdict. This is not what the driver runs (that is chip_smoke.py on
one chip).
"""

from __future__ import annotations

import contextlib
import gc
import hashlib
import json
import sys
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

RUNS = (
    ("1b_tp1", "llama-3.2-1b-instruct", None),
    ("1b_tp4", "llama-3.2-1b-instruct", {"tp": 4}),
    ("8b_tp4", "llama-3.1-8b-instruct", {"tp": 4}),
)


def _placement(array) -> dict:
    sharding = array.sharding
    return {
        "sharding": type(sharding).__name__,
        "devices": len(sharding.device_set),
        "spec": str(getattr(sharding, "spec", None)),
    }


@contextlib.contextmanager
def _recording():
    """What this check needs and the smoke does not: every wave's emitted
    token ids (greedy, so their digest is the cross-layout identity — the
    one `bench.py --preset tp-serving` asserts) and, while the engine is
    live, where its params / paged KV / prefix buffer sit. Read at the
    engine's harvest seam, for the duration of one chip_smoke.run()."""
    from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine

    seen = {"emitted": [], "placement": {}}
    harvest = InferenceEngine.harvest_wave

    def recording_harvest(engine, handle):
        finished = harvest(engine, handle)
        seen["emitted"].extend(list(f.token_ids) for f in finished)
        seen["placement"] = {
            "params_wq": _placement(engine.params["layers"]["wq"]),
            "paged_kv": _placement(engine.kv.k),
            "prefix_kv": _placement(engine._prefix.k),
        }
        return finished

    InferenceEngine.harvest_wave = recording_harvest
    try:
        yield seen
    finally:
        InferenceEngine.harvest_wave = harvest


def _run(model: str, mesh: dict | None) -> dict:
    import jax

    cfg = chip_smoke.smoke_config(model=model)
    cfg.data["llm"]["temperature"] = 0.0  # greedy: the digest is an identity
    if mesh is not None:
        cfg.data["llm"]["mesh"] = mesh
    try:
        with _recording() as seen:
            summary = chip_smoke.run(cfg)
        emitted = seen["emitted"]
        if not emitted or not all(emitted):
            summary["failures"].append("a wave emitted an empty decision")
        summary["placement"] = seen["placement"]
        summary["tokens_emitted"] = sum(map(len, emitted))
        # order-independent, as bench.py's tp-serving digest
        summary["token_digest"] = hashlib.sha256(
            json.dumps(sorted(emitted)).encode()
        ).hexdigest()[:16]
        return summary
    except Exception as exc:
        return {
            "failures": [f"died: {type(exc).__name__}: {exc}"[:2000]],
            "traceback": traceback.format_exc()[-6000:],
        }
    finally:
        gc.collect()
        jax.clear_caches()  # drop the finished run's executables and buffers


def main() -> int:
    import jax

    from k8s_llm_scheduler_tpu.logging_setup import setup_logging

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < 4:
        print(f"chip_tp4: needs four TPU chips, found {devices}", file=sys.stderr)
        return 2
    setup_logging(level="WARNING")
    record = {}
    for name, model, mesh in RUNS:
        record[name] = _run(model, mesh)
        print(json.dumps({name: record[name]}, default=str), flush=True)

    problems = [f"{n}: {f}" for n, r in record.items() for f in r["failures"]]
    if not problems:
        if record["1b_tp4"]["token_digest"] != record["1b_tp1"]["token_digest"]:
            problems.append(
                f"1B greedy digest tp=4 {record['1b_tp4']['token_digest']} != "
                f"tp=1 {record['1b_tp1']['token_digest']}"
            )
        for name in ("1b_tp4", "8b_tp4"):
            for what, place in record[name]["placement"].items():
                if place["sharding"] != "NamedSharding" or place["devices"] != 4:
                    problems.append(f"{name}: {what} placed {place}")
            used = record[name]["bytes_in_use_per_device"]
            if min(used) <= 0 or max(used) > 1.5 * min(used):
                problems.append(f"{name}: per-device bytes_in_use {used} not within 1.5x")
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / "chip_tp4.json").write_text(json.dumps(record, indent=1, default=str))
    keys = ("token_digest", "tokens_emitted", "bytes_in_use_per_device",
            "peak_bytes_in_use", "peak_bytes_after_build", "setup_seconds",
            "serve_seconds", "decisions_by_source", "attention_impls")
    print(json.dumps({
        "ok": not problems, "problems": problems,
        "runs": {n: {k: r.get(k) for k in keys} for n, r in record.items()},
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                   "count": len(devices)},
    }))
    return 0 if not problems else 1


if __name__ == "__main__":
    sys.exit(main())
