"""On-chip check: every Pallas kernel compiled by Mosaic vs its XLA reference.

The hermetic suite runs the kernels in the interpreter, which accepts
layouts Mosaic refuses. This runs each kernel a serving option can reach,
compiled (ops.pallas_interpret resolves to Mosaic on tpu), at the per-layer
shapes of llama-3.2-1b (head_dim 64) and llama-3.1-8b (head_dim 128),
unsharded and at the per-shard head counts of tp=4, and compares with the
XLA path on the same inputs under the tolerances of
tests/test_pallas_attention.py / test_ragged_matmul.py (one of them, the
flash-prefix unnormalized accumulator's absolute bound, scaled with
sqrt(prefix length) beyond the suite's Sp = 256 — see check_flash_prefix).

    python tools/chip_kernels.py          # needs a TPU; exits 1 on any failure

One JSON line per case, then a summary line; the full record also goes to
chiprun_out/chip_kernels.json. A compiler refusal is a failed case carrying
the compiler's message, not a crash.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from k8s_llm_scheduler_tpu.ops import attention as A  # noqa: E402
from k8s_llm_scheduler_tpu.ops.pallas_paged_attention import (  # noqa: E402
    paged_decode_attention_pallas,
    paged_decode_attention_parts,
)
from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import (  # noqa: E402
    flash_causal_attention_parts,
    flash_prefix_attention_parts,
)
from k8s_llm_scheduler_tpu.ops.ragged_matmul import ragged_matmul  # noqa: E402

# (n_heads, n_kv_heads, head_dim, d_model, d_ff) — models/configs.py
MODELS = {
    "llama-3.2-1b": (32, 8, 64, 2048, 8192),
    "llama-3.1-8b": (32, 8, 128, 4096, 14336),
}
DTYPE = jnp.bfloat16  # the serving dtype of both configs


def _normal(key, shape, dtype=DTYPE):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype)


def _close(got, ref, rtol, atol) -> float:
    """Max violation of |got-ref| <= atol + rtol*|ref| (<= 0 passes). Each
    check returns these per compared quantity; `info_` keys are reported,
    not judged."""
    got = np.asarray(got, dtype=np.float32)
    ref = np.asarray(ref, dtype=np.float32)
    return float(np.max(np.abs(got - ref) - (atol + rtol * np.abs(ref))))


def check_flash_prefix(H, KV, hd, B, S, Sp, plen):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _normal(ks[0], (B, S, H, hd))
    pk = _normal(ks[1], (Sp, KV, hd))
    pv = _normal(ks[2], (Sp, KV, hd))
    o, m, l = flash_prefix_attention_parts(q, pk, pv, jnp.int32(plen))
    qg = (q.astype(jnp.float32) * hd**-0.5).reshape(B, S, KV, H // KV, hd)
    mask = (jnp.arange(Sp) < plen)[None, None, None, None, :]
    with jax.default_matmul_precision("highest"):
        o_r, m_r, l_r = A.attend_part(qg, pk, pv, mask, "bqkgh,skh->bkgqs")
    # the same XLA path as it is served: default matmul precision (bf16
    # passes on the MXU) — the XLA arithmetic the kernel is measured beside
    o_x, _, _ = A.attend_part(qg, pk, pv, mask, "bqkgh,skh->bkgqs")

    def norm(o_, l_):
        return o_ / jnp.maximum(l_[..., None], 1e-30)

    # The UNNORMALIZED accumulator sums `plen` bf16-rounded terms, so its
    # rounding error grows like sqrt(keys). The suite's absolute 5e-2 was
    # set at Sp = 256; the same bound is carried to longer prefixes as
    # 5e-2 * sqrt(plen / 256) (identical at the suite's own shapes). What
    # the unscaled bound says — for the kernel and for XLA's own bf16
    # arithmetic on the same inputs — is reported beside it, not judged.
    atol_o = 5e-2 * max(1.0, (plen / 256) ** 0.5)
    return {
        "m": _close(m, m_r, 2e-2, 1e-2),
        "l": _close(l, l_r, 2e-2, 1e-2),
        "o": _close(o, o_r, 5e-2, atol_o),
        "o_normalized": _close(norm(o, l), norm(o_r, l_r), 5e-2, 5e-2),
        "info_o_at_suite_atol": _close(o, o_r, 5e-2, 5e-2),
        "info_xla_default_o_at_suite_atol": _close(o_x, o_r, 5e-2, 5e-2),
    }


def check_flash_causal(H, KV, hd, B, S, lens):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _normal(ks[0], (B, S, H, hd))
    k = _normal(ks[1], (B, S, KV, hd))
    v = _normal(ks[2], (B, S, KV, hd))
    lens = jnp.asarray(lens, jnp.int32)
    o, m, l = flash_causal_attention_parts(q, k, v, lens)
    qg = (q.astype(jnp.float32) * hd**-0.5).reshape(B, S, KV, H // KV, hd)
    pos = jnp.arange(S)
    mask = (pos[:, None] >= pos[None, :])[None, None, None] & (
        pos[None, :] < lens[:, None]
    )[:, None, None, None, :]
    with jax.default_matmul_precision("highest"):
        o_r, m_r, l_r = A.attend_part(qg, k, v, mask, "bqkgh,bskh->bkgqs")
    out = np.asarray(o / jnp.maximum(l[..., None], 1e-30))
    ref = np.asarray(o_r / jnp.maximum(l_r[..., None], 1e-30))
    worst = {"o_normalized": -np.inf, "m": -np.inf}
    for b, n in enumerate(np.asarray(lens)):  # rows past a row's length are garbage on both paths
        worst["o_normalized"] = max(
            worst["o_normalized"],
            _close(out[b, :, :, :n], ref[b, :, :, :n], 5e-2, 5e-2),
        )
        worst["m"] = max(
            worst["m"],
            _close(np.asarray(m)[b, :, :, :n], np.asarray(m_r)[b, :, :, :n], 2e-2, 1e-2),
        )
    return worst


def check_paged(H, KV, hd, parts: bool):
    B, num_pages, page_size, max_pages = 9, 128, 128, 8
    rng = np.random.default_rng(2)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _normal(ks[0], (B, H, hd))
    kc = _normal(ks[1], (num_pages, page_size, KV, hd))
    vc = _normal(ks[2], (num_pages, page_size, KV, hd))
    ids = rng.choice(np.arange(1, num_pages), size=(B, max_pages), replace=False)
    pt = jnp.asarray(ids.astype(np.int32))
    sl = jnp.asarray(
        [1, 127, 128, 129, 500, 1024, 71, 256, 900], dtype=jnp.int32
    )
    with jax.default_matmul_precision("highest"):
        ref = A.paged_decode_attention(q, kc, vc, pt, sl)
    if parts:
        o, m, l = paged_decode_attention_parts(q, kc, vc, pt, sl)
        got = A.merge_attention_parts([(o, m, l)]).reshape(B, H, hd)
    else:
        got = paged_decode_attention_pallas(q, kc, vc, pt, sl)
    return {"out": _close(got, ref, 2e-2, 2e-2)}  # the suite's bf16-input tolerance


def check_ragged(D_in, D_out, int8: bool):
    M, total = 192, 73  # R*F = 8*24 block rows, DFA-decided valid count
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = _normal(ks[0], (M, D_in))
    w = _normal(ks[1], (D_in, D_out)) * D_in**-0.5
    if int8:
        from k8s_llm_scheduler_tpu.models.quant import quantize_weight

        w = quantize_weight(w)
        w_ref = w["q"].astype(jnp.float32) * w["scale"].reshape(1, -1)
    else:
        w_ref = w.astype(jnp.float32)
    got = ragged_matmul(x, w, jnp.int32(total))
    with jax.default_matmul_precision("highest"):
        ref = x.astype(jnp.float32) @ w_ref
    done = -(-total // 64) * 64  # rows past the last computed M-tile are zero
    return {
        "out": _close(got[:done], ref[:done], 2e-2, 2e-2),
        "tail_abs": float(np.max(np.abs(np.asarray(got[done:], np.float32)))),
    }


def cases():
    for model, (H, KV, hd, D, F) in MODELS.items():
        for tp in (1, 4):
            h, kv = H // tp, KV // tp
            tag = f"{model}/tp{tp}"
            # wave suffix prefill, wave block decode, 2048-token prefix chunk
            for B, S, Sp, plen in ((8, 256, 2048, 1934), (8, 24, 2048, 1934),
                                   (1, 2048, 6144, 4000)):
                yield (f"flash_prefix {tag} q[{B},{S},{h},{hd}] Sp={Sp}",
                       check_flash_prefix, (h, kv, hd, B, S, Sp, plen))
            yield (f"flash_causal {tag} q[8,256,{h},{hd}]", check_flash_causal,
                   (h, kv, hd, 8, 256, [256, 71, 1, 200, 255, 128, 129, 64]))
            yield (f"flash_causal {tag} q[1,2048,{h},{hd}]", check_flash_causal,
                   (h, kv, hd, 1, 2048, [1999]))
            yield (f"paged_decode {tag} q[9,{h},{hd}]", check_paged, (h, kv, hd, False))
            yield (f"paged_decode_parts {tag} q[9,{h},{hd}]", check_paged, (h, kv, hd, True))
        for int8 in (False, True):
            kind = "int8" if int8 else "bf16"
            yield (f"ragged_matmul {model} [192,{D}]x[{D},{F}] {kind}",
                   check_ragged, (D, F, int8))
            yield (f"ragged_matmul {model} [192,{F}]x[{F},{D}] {kind}",
                   check_ragged, (F, D, int8))


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_kernels: needs a TPU, found {jax.devices()}", file=sys.stderr)
        return 2
    results = []
    for name, fn, args in cases():
        t0 = time.perf_counter()
        try:
            excess = {k: round(v, 5) for k, v in fn(*args).items()}
            judged = [v for k, v in excess.items() if not k.startswith("info_")]
            row = {"case": name, "ok": max(judged) <= 0, "excess": excess}
        except Exception as exc:  # a Mosaic refusal is a result, not a crash
            row = {"case": name, "ok": False, "error": f"{type(exc).__name__}: {exc}"[:1500]}
        row["seconds"] = round(time.perf_counter() - t0, 2)
        results.append(row)
        print(json.dumps(row), flush=True)
    failed = [r["case"] for r in results if not r["ok"]]
    summary = {
        "ok": not failed,
        "cases": len(results),
        "failed": failed,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "jax": jax.__version__,
    }
    out = Path(__file__).resolve().parent.parent / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / "chip_kernels.json").write_text(
        json.dumps({"summary": summary, "results": results}, indent=1)
    )
    print(json.dumps(summary))
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main())
