"""Device time of the operations under scope `gdn` (a gated-delta-rule
mixer whole: projections, convolution, the chunked scan, the gated norm and
W_o; nine of the cell's twelve mixers, models/gdn_moe.py), in every program
of the slice, per bind acknowledged in the traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "gdn")
