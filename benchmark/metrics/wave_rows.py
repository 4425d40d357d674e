"""Decisions to a wave, over the waves harvested in the window (engine worker)."""


def read(ctx):
    return sum(w["rows"] for w in ctx.waves) / len(ctx.waves) if ctx.waves else None
