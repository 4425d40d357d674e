"""What the families with a per-sequence state beside the per-token cache
(models/gdn_moe.py, models/mamba2_hybrid.py) share of it: the counters a
wave books, and the short convolution's window cut at each row's valid
length."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# What the state-space layers of a wave count: positions that were valid
# (sum of suffix_lens and of blk_len over the model calls, once a call, not
# a layer) and positions the chunked scan ran over, padding included. The
# names benchmark/metrics/state_valid_share.py reads.
STATE_COUNTERS = ("state_tokens_valid", "state_tokens_computed")


def window_at(xx: jax.Array, lens: jax.Array, width: int) -> jax.Array:
    """Row r's `width` entries of xx [B, width + S, C] that end at its valid
    length: xx[r, lens[r] : lens[r] + width] (xx starts with the window the
    call was handed, so a row of length 0 keeps it)."""
    idx = lens[:, None] + jnp.arange(width)[None, :]
    return jnp.take_along_axis(xx, idx[:, :, None], axis=1)
