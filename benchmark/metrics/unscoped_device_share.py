"""Share of the device's busy time in the traced slice that ran under no
scope of the program, in percent (device): relayouts at the top of a
program, eager operations. It says how much of the device's time the five
per-scope metrics and the other scopes cannot name."""

from metrics import _scope_trace


def read(ctx):
    r = _scope_trace.reduced(ctx)
    if r is None or not r["busy_s"]:
        return None
    return 100.0 * r["scopes"].get(_scope_trace.NO_SCOPE, 0.0) / r["busy_s"]
