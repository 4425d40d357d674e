"""What a gap between a sparse-expert program and its reference is made of
(architecture `mla_moe`), on the chip at the configuration's own size: does
the program route a token to other experts than the reference does, where,
and what is left of the gap when it is handed the reference's routes?

    python3 benchmark/tests/read_flips.py glm-4_7-flash 8
    JAX_PLATFORMS=cpu python3 benchmark/tests/read_flips.py <file.json> 2

A router takes the k largest of 64 scores. Where the k-th and the next lie
closer than the arithmetic before them resolves, bf16 operands pick another
expert than float32 does: a FLIP, which moves that token's stream by an
expert's whole output, far more than rounding does. This reads, over rows
of a recorded wave of the cell's own prompts (data/waves-backlog20.json:
the prefix and pod suffixes as the scheduler renders them, served tokens
from a toy model under the same grammar), each row as prefix + tail through

- the reference in float32 (reference/mla_moe.py, its selections and the
  margin between the k-th and the next score + bias),
- the PROGRAM's own layer (models/mla_moe.py `_layer`, one jitted program a
  layer, absorbed attention, the grouped-matmul kernels) free, and again
  with the reference's selections given to its router (`route(sel=)`),
- the reference's int8 control, free,

and prints one JSON line: flips by layer, the reference's margin at a
token's FIRST flip against the margin of all tokens (a flip is a near-tie
or it is a fault), and at every position that predicts a served token the
gap of the token each puts first under the reference's best (over the
tokenizer's ids; the cell's `mean_gap` is the same gap over the grammar's
choices), free, forced, and apart for rows with and without a flip of their
own. The weights are the program's own init (tests/check_init.py holds the
reference's equal to them, leaf for leaf); limits/<config>.json cites the
readings.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import seam
    from k8s_llm_scheduler_tpu.engine import local
    from k8s_llm_scheduler_tpu.models import mla_moe
    from k8s_llm_scheduler_tpu.models.configs import get_config
    from k8s_llm_scheduler_tpu.models.llama import rms_norm

    name = sys.argv[1]
    conf = seam.load_config(name if name.endswith(".json") else BENCH / "configs" / f"{name}.json")
    n_rows = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    ref = seam.reference(conf)
    cfg = get_config(seam.program(conf).register(conf))
    wave = json.loads((BENCH / "tests" / "data" / "waves-backlog20.json").read_text())
    prefix, vocab_rows = wave["prefix_ids"], wave["vocab_rows"]
    t0 = time.time()
    params = local._init_params(conf["weights_seed"], cfg)
    jax.block_until_ready(params)
    init_s = time.time() - t0

    S = -(-(len(prefix) + max(len(t) for t in wave["tails"])) // 512) * 512  # the reference attends in blocks of 512
    k, n_dense = cfg.n_experts_per_tok, cfg.n_dense_layers
    dims = ref._dims(conf)
    inv_freq = mla_moe._inv_freq(cfg)
    positions = jnp.arange(S)[None]
    causal = (jnp.arange(S)[:, None] >= jnp.arange(S)[None, :])[None, None]

    # ---- the program, a layer at a time (what lax.scan's body is handed)
    def layer_fn(lp, x, valid, forced, moe):
        taken = []
        real = mla_moe.route

        def spy(lp_, cfg_, h):
            sel, w = real(lp_, cfg_, h, sel=forced)
            taken.append(sel)
            return sel, w

        def attend(lp_, q_nope, q_rope, c_kv, k_r):
            return mla_moe.attend_absorbed(lp_, cfg, q_nope, q_rope,
                                           [(c_kv, k_r, causal & valid[:, None, None, :])])

        mla_moe.route = spy
        try:
            x, _, _ = mla_moe._layer(lp, cfg, x, positions, valid, inv_freq, moe, attend)
        finally:
            mla_moe.route = real
        return x, (taken[0] if taken else None)

    run_layer = jax.jit(layer_fn, static_argnames=("moe",))

    @jax.jit
    def program_head(x_rows):
        h = rms_norm(x_rows.astype(cfg.dtype), params["final_norm"], cfg.rms_eps)
        return jnp.einsum("td,dv->tv", h, params["lm_head"][:, :vocab_rows],
                          preferred_element_type=jnp.float32)

    def program(tokens, valid, rows, forced=None):
        x = mla_moe._stream(params, tokens[None])
        sels = []
        for i in range(cfg.n_layers):
            stack, j = ("dense_layers", i) if i < n_dense else ("moe_layers", i - n_dense)
            lp = {name: (leaf if name in mla_moe.EXPERT_LEAVES else leaf[j])
                  for name, leaf in params[stack].items()}
            lp["layer"] = jnp.int32(j)
            given = None if forced is None or i < n_dense else jnp.asarray(forced[i - n_dense])
            x, sel = run_layer(lp, x, valid[None], given, moe=i >= n_dense)
            if sel is not None:
                sels.append(np.sort(np.asarray(sel), axis=1))
        return np.asarray(program_head(x[0][rows])), sels

    # ---- the reference, a layer at a time (reference/mla_moe.py wave_logits' loop)
    @jax.jit
    def ref_select(x, lw):
        h = ref._rms_norm(x, lw["mlp_norm"], conf["rms_norm_eps"])
        s = jax.nn.sigmoid(jnp.matmul(h, lw["router"].astype(jnp.float32), precision="highest"))
        top, sel = jax.lax.top_k(s + lw["router_bias"], k + 1)
        return sel[:, :k], top[:, k - 1] - top[:, k]

    def reference(tokens, seg, rows, mode):
        emb = params["embed"][tokens]
        x = emb.astype(jnp.float32) if mode == "f32" else ref._int8_round(params["embed"])[tokens]
        pos = jnp.arange(S)
        sels, margins = [], []
        for i in range(cfg.n_layers):
            stack, j = ("dense_layers", i) if i < n_dense else ("moe_layers", i - n_dense)
            lw = {name: leaf[j] for name, leaf in params[stack].items()}
            x = ref._attention(x, lw, pos, seg, dims, mode)
            if i < n_dense:
                x = ref._dense_ffn(x, lw, dims, mode)
            else:
                sel, margin = ref_select(x, lw)
                sels.append(np.asarray(sel))
                margins.append(np.asarray(margin))
                x = ref._expert_ffn(x, lw, dims, mode)
        logits = ref._head(x[rows], params["final_norm"], params["lm_head"],
                           conf["rms_norm_eps"], mode, vocab_rows)
        return np.asarray(logits), sels, margins

    def gaps(best_of, low):
        return best_of.max(axis=1) - best_of[np.arange(len(low)), low.argmax(axis=1)]

    n_moe = cfg.n_moe_layers
    out = {"program": [], "forced": [], "int8": []}
    flips = {"program": np.zeros(n_moe, int), "int8": np.zeros(n_moe, int)}
    first_margin = {"program": [], "int8": []}
    flipped_row = {"program": [], "int8": []}
    all_margins, tokens_seen = [], 0
    t0 = time.time()
    for tail, (first, count) in list(zip(wave["tails"], wave["spans"]))[:n_rows]:
        ids = list(prefix) + list(tail)
        n = len(ids)
        tokens = jnp.asarray(ids + [0] * (S - n), jnp.int32)
        valid = jnp.arange(S) < n
        seg = jnp.where(valid, 0, -1).astype(jnp.int32)
        rows = jnp.asarray([len(prefix) + first + j for j in range(count)])
        f32_logits, f32_sel, margins = reference(tokens, seg, rows, "f32")
        f32_sets = [np.sort(s, axis=1) for s in f32_sel]
        all_margins.extend(m[:n] for m in margins)
        tokens_seen += n
        free_logits, free_sel = program(tokens, valid, rows)
        forced_logits, _ = program(tokens, valid, rows, forced=f32_sel)
        int8_logits, int8_sel, _ = reference(tokens, seg, rows, "int8")
        out["program"].append(gaps(f32_logits, free_logits))
        out["forced"].append(gaps(f32_logits, forced_logits))
        out["int8"].append(gaps(f32_logits, int8_logits))
        for who, sels in (("program", free_sel), ("int8", [np.sort(s, axis=1) for s in int8_sel])):
            before = np.zeros(S, bool)
            for layer, (mine, theirs) in enumerate(zip(sels, f32_sets)):
                flip = (mine != theirs).any(axis=1) & np.asarray(valid)
                flips[who][layer] += int(flip.sum())
                first_margin[who].extend(margins[layer][flip & ~before].tolist())
                before |= flip
            flipped_row[who].append(before[np.asarray(rows)])

    def stats(g):
        g = np.concatenate(g)
        return {"mean_gap": float(g.mean()), "worst_gap": float(g.max()), "moved": int((g > 0).sum()),
                "positions": int(g.size)}

    def split(who):
        g, f = np.concatenate(out[who]), np.concatenate(flipped_row[who])
        return {"positions_with_a_flip_of_their_own": int(f.sum()),
                "mean_gap_there": float(g[f].mean()) if f.any() else None,
                "mean_gap_elsewhere": float(g[~f].mean()) if (~f).any() else None}

    def margin_stats(m):
        m = np.asarray(m)
        if not m.size:
            return None
        return {"n": int(m.size), "median": float(np.median(m)), "p90": float(np.quantile(m, 0.9)),
                "max": float(m.max())}

    every = np.concatenate(all_margins)
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "config": conf["name"], "layers": cfg.n_layers,
        "rows": n_rows, "tokens": tokens_seen, "token_layers": tokens_seen * n_moe,
        "init_s": round(init_s, 1), "read_s": round(time.time() - t0, 1),
        "margin_of_every_token": margin_stats(every),
        "program": {"free": stats(out["program"]), "with_the_references_routes": stats(out["forced"]),
                    "flips_by_layer": flips["program"].tolist(),
                    "margin_at_first_flip": margin_stats(first_margin["program"]), **split("program")},
        "int8_control": {"free": stats(out["int8"]), "flips_by_layer": flips["int8"].tolist(),
                         "margin_at_first_flip": margin_stats(first_margin["int8"]), **split("int8")},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
