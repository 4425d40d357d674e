"""XLA programs compiled inside the window (it should read 0)."""


def read(ctx):
    return float(ctx.delta("compiles", "programs"))
