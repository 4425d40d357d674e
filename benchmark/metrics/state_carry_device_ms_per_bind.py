"""Device time of carrying a per-sequence state beside the per-token cache:
scope `state_seed` (a wave's rows each copied from the pinned prefix's
state, once a suffix call) + scope `state_writeback` (each row's
convolution window cut at its valid length, once a delta-rule layer a
call), per bind acknowledged in the traced slice (model). The state's own
read and write lie in the scan that uses it (`gdn_scan`). None where a
program names neither scope."""

from metrics import _scope_trace


def read(ctx):
    parts = [_scope_trace.per_bind_ms(ctx, s) for s in ("state_seed", "state_writeback")]
    return None if all(p is None for p in parts) else sum(p or 0.0 for p in parts)
