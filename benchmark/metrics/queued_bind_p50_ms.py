"""ADD event -> bind acknowledged, median over the pods bound in the window
(closed loop: mostly time spent queued behind the standing backlog)."""


def read(ctx):
    out, acks = ctx.outcome, ctx.cluster.acks
    lat = sorted(1000.0 * (acks[p] - out.sent[p]) for p in out.sent
                 if p in acks and out.t0 <= acks[p] < out.t1)
    return lat[(len(lat) - 1) // 2] if lat else None
