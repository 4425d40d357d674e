"""Block-decode model calls to a wave: engine.stats' count (booked at
harvest) over the waves harvested in the window."""


def read(ctx):
    if not ctx.waves:
        return None
    return ctx.delta("sched", "client", "engine", "wave_model_calls") / len(ctx.waves)
