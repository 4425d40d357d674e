"""Grouped Pallas matmuls for a sparse-expert feed-forward: rows sorted by
expert against that expert's weights, work in proportion to the rows.

A routed layer sends each token to a few of its experts. Sorted by expert,
the token copies form contiguous row groups of `x` [M, K], group g to be
multiplied by `w[g]` [K, N]. The sizes are decided ON DEVICE by the router,
so shapes stay static and the kernel is told where the groups lie:

- a WORK ITEM is one (row tile, group) pair that overlap. There are at most
  `M/tm + G - 1` of them; `group_metadata` lists them in order (scalar
  prefetch), and items past the last real one repeat it, so they start no
  copy and `pl.when` skips their arithmetic;
- grid (N/tn, items), items innermost: for one column tile the items walk
  the groups in order, so each touched expert's [K, tn] weight block is
  copied HBM->VMEM exactly once, and AN EXPERT NO ROW WAS SENT TO IS NEVER
  READ. In block decode a few dozen valid tokens touch about half the
  experts; the rest of their weights stay in HBM. Consecutive items of one
  row tile keep its output block resident and write only their own rows;
- rows past the last group (assignments of padding tokens, sorted behind
  every expert) belong to no item: their output rows are NEVER WRITTEN and
  hold whatever the buffer held. The caller masks them with `where`, never
  with a multiplication.

`swiglu=True` takes two weights and writes silu(x w0) * (x w1): gate and up
of one expert in one pass over x.

The weights are the WHOLE STACK [L, G, K, N] of a layer scan and `layer`
says which layer's to use: the index is one more prefetched scalar, read by
the weight block's index map, so the kernel copies its blocks straight out
of the stacked array. Handing the kernel a layer's slice instead makes XLA
copy that slice out first (a custom call's operand has to be contiguous):
604 MB a layer a model call at the published widths, which the first run on
the chip showed as 68% of the device's time (PERF.md §6, PR 30).

Equivalence against a loop over experts: tests/test_mla_moe.py (interpret
mode on the CPU, the same code path the chip compiles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_llm_scheduler_tpu.ops import pallas_interpret

ROW_TILE = 128


def group_metadata(group_sizes: jax.Array, m: int, tm: int):
    """(item_group [W], item_tile [W], offsets [G+1], n_items [1]) for rows
    sorted by group over `m` rows in tiles of `tm`; W = m/tm + G - 1."""
    G = group_sizes.shape[0]
    tiles_m = m // tm
    W = tiles_m + G - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tm
    n_tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - first_tile, 0)
    item_end = jnp.cumsum(n_tiles)
    item_start = item_end - n_tiles
    n_items = item_end[-1]
    # items past the last real one repeat it: same blocks, no new copy
    i = jnp.minimum(jnp.arange(W, dtype=jnp.int32), jnp.maximum(n_items - 1, 0))
    g = jnp.minimum(jnp.searchsorted(item_end, i, side="right"), G - 1).astype(jnp.int32)
    t = jnp.clip(first_tile[g] + (i - item_start[g]), 0, tiles_m - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return g, t, offsets, n_items.reshape(1)


def _kernel(group_ref, tile_ref, offs_ref, n_ref, _layer_ref, x_ref, *refs, tm: int, swiglu: bool):
    *w_refs, o_ref = refs
    i = pl.program_id(1)

    @pl.when(i < n_ref[0])
    def _item():
        g = group_ref[i]
        rows = tile_ref[i] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (rows >= offs_ref[g]) & (rows < offs_ref[g + 1])
        x = x_ref[...]
        acc = jnp.dot(x, w_refs[0][0, 0], preferred_element_type=jnp.float32)
        if swiglu:
            up = jnp.dot(x, w_refs[1][0, 0], preferred_element_type=jnp.float32)
            acc = jax.nn.silu(acc) * up
        o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])


def _col_tile(n: int) -> int:
    for tn in (512, 256, 128):
        if n % tn == 0:
            return tn
    return n


# Mosaic's default scoped-VMEM limit on the chips served (v5e: 16 MiB), and
# the part of it the pipeline's double-buffered blocks may take before the
# call asks for more.
_VMEM_DEFAULT = 16 << 20
_VMEM_BLOCKS = 12 << 20


def _vmem_limit(tm: int, k: int, tn: int, n_weights: int, x_bytes: int, out_bytes: int) -> int | None:
    """None where the blocks fit the default limit (the [2048, 1536]
    experts: the call is compiled as before); else a limit that holds them
    double-buffered with the kernel's float32 temporaries. A [6144, 512]
    bf16 weight block is 6.3 MB, two weights double-buffered 25 MB: the
    chip's VMEM (128 MiB) holds it, the default limit does not, and a
    narrower block reads 256-byte rows."""
    blocks = 2 * (tm * k * x_bytes + n_weights * k * tn * x_bytes + tm * tn * out_bytes)
    if blocks <= _VMEM_BLOCKS:
        return None
    return blocks + 3 * tm * tn * 4 + (_VMEM_DEFAULT - _VMEM_BLOCKS)


@functools.partial(jax.jit, static_argnames=("swiglu", "out_dtype", "interpret"))
def grouped_matmul(
    x: jax.Array,            # [M, K] rows sorted by group, rows of no group last
    weights: tuple,          # (w,) or, with swiglu, (w_gate, w_up); each [L, G, K, N]
    group_sizes: jax.Array,  # [G] int32 rows of each group
    layer: jax.Array | int = 0,  # which of the L stacked layers' weights
    *,
    swiglu: bool = False,
    out_dtype=None,
    interpret: bool | None = None,
) -> jax.Array:
    """out[rows of group g] = x[rows] @ w[layer, g] (or silu(x w0[layer, g]) *
    (x w1[layer, g])), [M, N]. Rows of no group are not written: mask them,
    do not scale them."""
    interpret = pallas_interpret(interpret)
    M, K = x.shape
    _L, G, Kw, N = weights[0].shape
    assert K == Kw and len(weights) == (2 if swiglu else 1), (x.shape, weights[0].shape)
    tm = min(ROW_TILE, -(-M // 16) * 16)
    m_pad = -(-M // tm) * tm
    if m_pad != M:
        x = jnp.pad(x, ((0, m_pad - M), (0, 0)))
    tn = _col_tile(N)
    item_group, item_tile, offsets, n_items = group_metadata(group_sizes, m_pad, tm)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    w_spec = pl.BlockSpec((1, 1, K, tn), lambda n, i, g, t, o, c, l: (l[0], g[i], 0, n))
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    limit = _vmem_limit(tm, K, tn, len(weights), x.dtype.itemsize, out_dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, swiglu=swiglu),
        name="moe_grouped_swiglu" if swiglu else "moe_grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // tn, item_group.shape[0]),
            in_specs=[pl.BlockSpec((tm, K), lambda n, i, g, t, o, c, l: (t[i], 0))]
            + [w_spec] * len(weights),
            out_specs=pl.BlockSpec((tm, tn), lambda n, i, g, t, o, c, l: (t[i], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, N), out_dtype),
        compiler_params=None if limit is None else pltpu.CompilerParams(vmem_limit_bytes=limit),
        interpret=interpret,
    )(item_group, item_tile, offsets, n_items, layer, x, *weights)
    return out[:M]
