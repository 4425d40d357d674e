"""Device time in all-reduce / all-gather / reduce-scatter events over device busy time."""


def read(ctx):
    t = ctx.trace
    if t is None or t["devices"] < 2 or not t["busy_s"]:
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
