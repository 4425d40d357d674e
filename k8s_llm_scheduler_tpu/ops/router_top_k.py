"""A router's top k as one Pallas kernel: each row's k largest selection
scores, and the router's scores at those outputs, without a sort.

`jax.lax.top_k` lowers on the TPU to a full sort of every row: 26 µs for a
decode call's [192, 512] scores, where the k picks are k row maxima. A grid
step holds a tile of rows' scores in VMEM and runs k rounds there: the row's
maximum of `scores + bias`, the lowest output that holds it, that output's
unbiased score, and the output masked before the next round. The picks are
exactly `lax.top_k`'s: largest first, the lower index first on a tie (tests/
test_routed_order.py), and each weight is the score at its pick, bit for
bit (one score and zeros summed). On a TPU v5e (PERF.md §6) the picks
alone took 1.6 / 12 / 24 µs at the three calls of a 512-output router (192,
1,024 and 2,048 rows) where the sort took 28 / 92 / 179 µs and k rounds of
XLA reductions 12 / 37 / 68.

Scores are finite (a sigmoid or a softmax, plus a finite bias).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from k8s_llm_scheduler_tpu.ops import pallas_interpret

# Rows a grid step holds: a prefill's 1,024 or 2,048 tokens in tiles of 256,
# a decode call's 192 whole. Picks are written across a lane row of 128.
ROW_TILE = 256
LANES = 128


def _kernel(*refs, k: int, biased: bool):
    if biased:
        s_ref, b_ref, idx_ref, val_ref = refs
    else:
        s_ref, idx_ref, val_ref = refs
    s = s_ref[...]
    x = s + b_ref[...] if biased else s
    n = x.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1).astype(jnp.float32)
    lanes = jax.lax.broadcasted_iota(jnp.int32, idx_ref.shape, 1)
    idx = jnp.zeros(idx_ref.shape, jnp.float32)
    val = jnp.zeros(val_ref.shape, jnp.float32)
    for j in range(k):
        top = jnp.max(x, axis=1, keepdims=True)
        pick = jnp.min(jnp.where(x == top, cols, float(n)), axis=1, keepdims=True)
        hit = cols == pick
        idx = jnp.where(lanes == j, pick, idx)
        val = jnp.where(lanes == j, jnp.sum(jnp.where(hit, s, 0.0), axis=1, keepdims=True), val)
        x = jnp.where(hit, -jnp.inf, x)
    idx_ref[...] = idx.astype(jnp.int32)
    val_ref[...] = val


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def router_top_k(scores: jax.Array, bias: jax.Array | None, k: int,
                 interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """(indices [T, k] int32, scores at them [T, k] f32) of the k largest
    `scores + bias` of each row of scores [T, n] (f32; bias [n] or None):
    `jax.lax.top_k(scores + bias, k)[1]` and `take_along_axis(scores, ..)`.
    Jitted, so that a process traces and lowers the kernel once a shape,
    not once a layer of each program: ~50 ms each time (set-up)."""
    T, n = scores.shape
    if not 0 < k <= min(n, LANES):
        raise ValueError(f"top {k} of {n} outputs: k must lie in 1..{min(n, LANES)}")
    tm = min(T, ROW_TILE)
    rows = -(-T // tm) * tm  # a row count the tile does not divide: padding rows, cut off below
    row = pl.BlockSpec((tm, n), lambda r: (r, 0))
    out = pl.BlockSpec((tm, LANES), lambda r: (r, 0))
    operands = [scores.astype(jnp.float32)]
    if rows > T:
        operands[0] = jnp.pad(operands[0], ((0, rows - T), (0, 0)))
    in_specs = [row]
    if bias is not None:
        operands.append(bias.astype(jnp.float32).reshape(1, n))
        in_specs.append(pl.BlockSpec((1, n), lambda r: (0, 0)))
    idx, val = pl.pallas_call(
        functools.partial(_kernel, k=k, biased=bias is not None),
        name="router_top_k",
        grid=(rows // tm,),
        in_specs=in_specs,
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(*operands)
    return idx[:T, :k], val[:T, :k]
