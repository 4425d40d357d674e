"""Of the prefix keys a causal layer would read, the share a window layer's
attention reads: `window_keys_read` (for each valid query, the prefix keys
the windowed attention visits: on the chip the key blocks the kernel's grid
walks, whole) ÷ `window_keys_causal` (for each, prefix_len), over the
window's waves, once a model call (models/cohere2_moe.py WINDOW_COUNTERS),
% (model). A kernel that visited every key block would read 100% or more.
A program that does not count them (a parent, another architecture) reads
None, not 0."""

from metrics import _moe


def read(ctx):
    causal = ctx.delta(*_moe.ENGINE, "window_keys_causal")
    if causal <= 0:
        return None
    return 100.0 * ctx.delta(*_moe.ENGINE, "window_keys_read") / causal
