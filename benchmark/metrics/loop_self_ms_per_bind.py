"""The event loop thread's synchronous work per bind: self time of the
`sched.*` spans that never cover an await (`sched.render`,
`sched.delta_encode`, `sched.tokenize`, `sched.cache_lookup`,
`sched.bind_call`), over the binds acknowledged in the traced slice (watch
and bind loop)."""

from metrics import _program_trace


def read(ctx):
    r = _program_trace.reduced(ctx)
    if r is None or not r["loop_spans"] or not r["binds"]:
        return None
    return 1000.0 * r["loop_self_s"] / r["binds"]
