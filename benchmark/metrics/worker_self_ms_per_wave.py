"""The engine worker thread's own work per wave: self time of its
`engine.*` spans other than the five that wait (`engine.queue_wait`,
`engine.admit_hold`, `engine.harvest_poll` less what nests in it,
`engine.harvest_wait`, and `engine.dispatch`: the calls that enqueue
device work block while the device's queue is full), over the waves
harvested in the traced slice (engine worker). How far the one worker
thread is from setting the pace."""

from metrics import _program_trace


def read(ctx):
    r = _program_trace.reduced(ctx)
    if r is None or not r["aligned"] or not r["harvests"]:
        return None
    return 1000.0 * r["worker_self_s"] / r["harvests"]
