"""Median `admission_wait` span (enqueue at the engine worker -> wave
dispatch) of the decisions whose traces the flight recorder still holds
from the window."""


def read(ctx):
    waits = ctx.admission_waits_ms
    if not waits:
        return None
    waits = sorted(waits)
    return waits[(len(waits) - 1) // 2]
