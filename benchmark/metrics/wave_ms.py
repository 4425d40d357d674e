"""Host clock from a wave's submit to its harvest, mean over the window's waves (engine)."""


def read(ctx):
    if not ctx.waves:
        return None
    return 1000.0 * sum(w["t_harvest"] - w["t_submit"] for w in ctx.waves) / len(ctx.waves)
