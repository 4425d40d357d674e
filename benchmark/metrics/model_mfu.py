"""The whole step's share of the chips' bf16 peak: FLOPs the model needs for
the non-padding tokens the window processed (prefix prefills, suffix
prefills, served tokens; harness/flops.py) over window seconds x peak x chips."""

from harness import flops


def read(ctx):
    if not ctx.waves:
        return None
    total = 0.0
    for w in ctx.waves:
        total += flops.wave_flops(ctx.conf, [len(p) for p in w["prompts"]],
                                  [len(s) for s in w["served"]], len(w["prefix_ids"]))
    out = ctx.outcome
    new = ctx.delta("sched", "client", "engine", "prefill_tokens") - sum(
        len(p) for w in ctx.waves for p in w["prompts"])
    n = sum(1 for t0, _t1, _n in ctx.prefix_prefills if out.t0 <= t0 < out.t1)
    if n and new > 0:
        mean_len = sum(k for t0, _t1, k in ctx.prefix_prefills if out.t0 <= t0 < out.t1) / n
        total += n * flops.prefix_prefill_flops(ctx.conf, int(new / n), int(mean_len))
    return 100.0 * total / (ctx.seconds * ctx.peaks["bf16_flops"] * ctx.chips)
