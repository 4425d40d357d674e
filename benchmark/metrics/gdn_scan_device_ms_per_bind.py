"""Device time of the operations under scope `gdn_scan` (the chunked delta
rule itself: L2 norms, the C x C products and the triangular solve of a
chunk, the products with the state, the state's update and read-out; what a
delta-rule layer pays beside its projections, models/gdn_moe.py
`gated_delta_chunks`), per bind acknowledged in the traced slice (model)."""

from metrics import _scope_trace


def read(ctx):
    return _scope_trace.per_bind_ms(ctx, "gdn_scan")
