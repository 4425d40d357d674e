"""Median of the scheduler's own `bind` phase over the window, from the
PhaseRecorder's log-spaced buckets (an upper bucket bound, so a coarse
number: a per-layer reading only)."""


def read(ctx):
    before = ctx.outcome.before["sched"]["phases"].get("bind", {}).get("_hist")
    after = ctx.outcome.after["sched"]["phases"].get("bind", {}).get("_hist")
    if after is None:
        return None
    counts = [a - (before["counts"][i] if before else 0) for i, a in enumerate(after["counts"])]
    total, acc = sum(counts), 0
    if total <= 0:
        return None
    for i, c in enumerate(counts):
        acc += c
        if acc >= 0.5 * total:
            return 1e-4 * 2**i * 1000.0
    return None
