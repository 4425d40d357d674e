"""Faults planted under the timed path, for tests/test_faults.py: the run
goes on as usual and `correct` has to come out false."""

from __future__ import annotations

FAULTS = ("altered_token",)


def plant(name: str, backend, probes) -> None:
    """altered_token: in every wave, row 0's node-name token at the depth
    where the names diverge is replaced, where the wave produces it (the
    device program's output), by another name's token."""
    if name != "altered_token":
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
    import jax.numpy as jnp
    import numpy as np

    engine, tok = backend.engine, backend.tokenizer
    wave = engine._wave
    paths = [tok.encode(n) for n in next(iter(backend._dfa_cache))]
    depth = 0
    while all(len(p) > depth for p in paths) and len({p[depth] for p in paths}) == 1:
        depth += 1
    index = len(tok.encode('{"selected_node": "')) + depth  # among the row's served tokens
    choices = sorted({p[depth] for p in paths if len(p) > depth})

    def altered(*args, **kwargs):
        toks, act, iters = wave(*args, **kwargs)
        row = np.asarray(toks[0])
        cols = np.flatnonzero(row != tok.pad_id)
        if len(cols) <= index:
            return toks, act, iters
        col = int(cols[index])
        other = next(c for c in choices if c != int(row[col]))
        return toks.at[0, col].set(jnp.int32(other)), act, iters

    engine._wave = altered
