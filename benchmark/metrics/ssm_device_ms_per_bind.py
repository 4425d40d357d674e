"""Device time of the operations under scope `ssm` (a Mamba-2 mixer whole:
W_in, the convolution, the chunked scan, the gated norm and W_out; 36 of
the cell's 40 mixers, models/mamba2_hybrid.py), in every program of the
slice, per bind acknowledged in the traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "ssm")
