"""Binds whose decision came from the model, as a share of binds (decision client)."""


def read(ctx):
    binds = ctx.delta("sched", "total_scheduled")
    return 100.0 * ctx.delta("sched", "llm_decisions") / binds if binds else None
