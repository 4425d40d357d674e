"""Ragged-M Pallas matmul: skip dead decode-block columns on the MXU.

SCALING.md's wave roofline derives that 62% of block-decode compute at the
250-token operating point is F-width padding: each grammar-accelerated
iteration processes an [R, F] token block, but only the first len_r tokens
of each row are valid — and those counts are decided ON DEVICE by the DFA
walk, so no host-side bucketing can remove the padding without a host
round trip per iteration.

This kernel is the fix the roofline names. The engine compacts the valid
tokens to the FRONT of the flattened [M=R*F, K] activation (one argsort per
iteration, shared by all layers — models/llama.block_decode), and every
projection/MLP matmul runs here with the valid-token count scalar-
prefetched:

- grid (N/bn, K/bk), K innermost: each weight tile streams HBM->VMEM
  exactly once per call — weight traffic is identical to a dense matmul
  (an M-outer ragged grid would re-stream the full weight per M-tile,
  which at decode batch sizes is the dominant byte cost);
- the whole M extent of x and out live in VMEM blocks (decode M = R*F is
  a few hundred rows);
- the kernel body loops over ceil(total/bm) M-tiles with a dynamic
  fori_loop bound — FLOPs scale with the REAL token count, rounded up to
  bm, instead of with F*R.

Weights may be bf16 arrays or the int8 weight-only pairs from
models/quant.py ({"q", "scale"}): the q tile is converted next to the MXU
and the per-output-channel scale is applied outside (same contract as
models/llama._dense).

Equivalence vs the XLA dense path: tests/test_ragged_matmul.py (interpret
mode on CPU, same code path the chip runs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_llm_scheduler_tpu.ops import pallas_interpret


def _kernel(total_ref, x_ref, w_ref, o_ref, *, bm: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    total = total_ref[0]
    m_tiles = (total + bm - 1) // bm
    w_tile = w_ref[...]
    if w_tile.dtype == jnp.int8:
        w_tile = w_tile.astype(jnp.bfloat16)

    def body(m, _):
        x_tile = x_ref[pl.ds(m * bm, bm), :]
        acc = jnp.dot(
            x_tile.astype(w_tile.dtype), w_tile,
            preferred_element_type=jnp.float32,
        )
        o_ref[pl.ds(m * bm, bm), :] += acc
        return 0

    jax.lax.fori_loop(0, m_tiles, body, 0, unroll=False)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    rem = -size % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def ragged_matmul(
    x: jax.Array,      # [M, K] activations, valid rows compacted to front
    w,                 # [K, N] bf16 | {"q": int8 [K, N], "scale": [1, N]}
    total: jax.Array,  # scalar int32: number of valid rows of x
    *,
    bm: int = 64,
    bn: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """out[:ceil(total/bm)*bm] = x @ w (+ dequant scale); rows beyond the
    last computed M-tile are ZERO. Output dtype follows x."""
    interpret = pallas_interpret(interpret)
    quantized = isinstance(w, dict)
    w_arr = w["q"] if quantized else w
    M, K = x.shape
    Kw, N = w_arr.shape
    assert K == Kw, (x.shape, w_arr.shape)
    bn = min(bn, _ceil_mult(N, 128))
    bk = min(bk, _ceil_mult(K, 128))
    xp = _pad_to(x, 1, bk)
    wp = _pad_to(_pad_to(w_arr, 0, bk), 1, bn)
    mp = _pad_to(xp, 0, bm)
    grid = (wp.shape[1] // bn, wp.shape[0] // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, bm=bm),
        name="ragged_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # index maps take (*grid, *scalar_prefetch_refs)
                pl.BlockSpec((mp.shape[0], bk), lambda n, k, _t: (0, k)),
                pl.BlockSpec((bk, bn), lambda n, k, _t: (k, n)),
            ],
            out_specs=pl.BlockSpec(
                (mp.shape[0], bn), lambda n, k, _t: (0, n)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((mp.shape[0], wp.shape[1]), jnp.float32),
        interpret=interpret,
    )(jnp.atleast_1d(total).astype(jnp.int32), mp, wp)
    out = out[:M, :N]
    if quantized:
        out = out * w["scale"].reshape(1, -1)
    return out.astype(x.dtype)


def _ceil_mult(n: int, mult: int) -> int:
    return -(-n // mult) * mult
