"""Prompt tokens actually prefilled (prefix tails and suffixes) to a finished decision."""


def read(ctx):
    done = ctx.delta("sched", "client", "engine", "completed")
    return ctx.delta("sched", "client", "engine", "prefill_tokens") / done if done else None
