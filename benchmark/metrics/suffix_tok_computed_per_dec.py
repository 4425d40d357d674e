"""Suffix tokens the device computes to a decision: a wave's rows times the
width its suffix prefill was compiled for, padding rows and columns
included (`engine.stats` `suffix_tokens_computed`), beside
prefill_tok_per_dec's real ones. Over the decisions the same waves carried
(`requests`, booked by the same submit), not those that finished in the
window: a traced run's window closes on a drained pipeline (the profiler's
stop holds the event loop while the waves in flight finish), so `completed`
counts ~5 waves the window did not submit (PERF.md §6, PR 33: 125 for 128).
None where the program has no such counter (it reads 0: a parent)."""


def read(ctx):
    sent = ctx.delta("sched", "client", "engine", "requests")
    computed = ctx.delta("sched", "client", "engine", "suffix_tokens_computed")
    return computed / sent if sent and computed else None
