"""How late the generator sent an ADD event against its due time, 95th percentile."""


def read(ctx):
    out = ctx.outcome
    late = sorted(1000.0 * (out.sent[p] - out.due[p]) for p in out.window_pods)
    return late[max(-(-95 * len(late) // 100) - 1, 0)] if late else None
