"""Device time of the operations under scope `ssm_scan` (the chunked
Mamba-2 recurrence itself: the decay's sums, the kernel `ssd_chunk_scan`
that reads and writes the state, and the skip D x; what a Mamba-2 layer
pays beside its projections, models/mamba2_hybrid.py `ssd_chunks`), per
bind acknowledged in the traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "ssm_scan")
