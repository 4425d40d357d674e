"""The Mamba-2 state-space recurrence (SSD) over chunks as one Pallas
kernel: a (row, block of heads)'s state is read from HBM once, kept in VMEM
across the call's chunks, and written once, where it lies; everything a
chunk needs beside it is made in VMEM from x, B, C and the step as they are.

For each head with state S [P, N] (head width P, state width N), token by
token: `S <- e^{dt_t A} S + dt_t x_t B_t^T`; `y_t = S C_t`
(models/mamba2_hybrid.py's layer equations; B and C are shared by every
head, the skip D x is the caller's). Over a chunk of T positions, with
gamma_t = sum_{i <= t} dt_i A (<= 0) and the state S_0 the chunk starts
from,

    Y   = e^gamma (C S_0^T) + (tril(e^{gamma_i - gamma_j}) * C B^T) (dt x)
    S_T = e^{gamma_T} S_0 + ((e^{gamma_T - gamma} dt) x)^T B.

Every exponent is a difference that is not positive: no solve, nothing
that grows. ops/gdn_scan.py is the skeleton:

- grid (rows, heads / block, chunks), the chunk axis last and sequential.
  The state's output block has the same index for every chunk of a (row,
  head block), so it stays in VMEM from the first chunk, which copies the
  input block into it, to the last, after which it is written back: one
  read and one write of the state a call, whatever the number of chunks (1
  in block decode, 2 in the suffix call, 32 in a prefix prefill). A chunk
  that holds no valid position of its row (`lens`) is passed over: it
  would leave the state as it was, and its Y is zero;
- THE PRODUCTS WITH THE STATE ARE ONE EACH FOR THE BLOCK'S HEADS. B and C
  are shared by every head, so the block's states stacked as [heads x P, N]
  meet C in one product (the read-out, [heads x P, T]) and the update is
  one product of the decayed inputs [heads x P, T] with B. The kernel
  keeps x and Y TRANSPOSED, a head's P on the sublanes and the chunk's
  positions on the lanes ([B, H, n, P, T]: the wrapper swaps the last two
  axes on the way in and out), so that a head is a slice of rows of either
  product. What differs by head, the decay inside the chunk, is a loop
  written out over the block's heads: tril(e^{gamma_i - gamma_j}) * C B^T,
  [T, T], and its product with the head's dt x;
- THE STATE IS A WHOLE MEMBER [periods, rows, H, P, N] of what a sequence
  carries and `period` says which entry to advance: a prefetched scalar
  read by the state's index maps, and the output ALIASED to the input, so
  the layer scan carries the members and each layer updates its entry in
  place; the other entries are never touched. Where the caller still needs
  the array it handed in (a pin's state), XLA copies it first;
- the arithmetic is ops/gdn_scan.py's: float32 throughout, the two
  products with the state in THREE bfloat16 passes (hi hi + lo hi + hi
  lo), C B^T and its product with dt x at `highest`. A position that is not
  valid has dt = 0: a decay of one and no input, so a row with no valid
  position gets its state back bit for bit.

Equivalence against the token-by-token recurrence: tests/test_mamba2_hybrid.py
(interpret mode on the CPU, the same code path the chip compiles);
compiled for a described chip at the published sizes:
tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_llm_scheduler_tpu.ops import pallas_interpret
from k8s_llm_scheduler_tpu.ops._f32dot import HIGHEST, dot3

# Heads a grid step holds: 16 x [64, 128] float32 = 512 KB of state, so a
# layer of 8 rows x 64 heads is 32 steps.
HEAD_BLOCK = 16


def _kernel(_period_ref, lens_ref, grow_ref, gcol_ref, drow_ref, x_ref, b_ref, c_ref, s_in_ref, y_ref, s_ref,
            w_ref, *, heads: int, chunk: int):
    b, c = pl.program_id(0), pl.program_id(2)

    @pl.when(c == 0)
    def _first_chunk():
        s_ref[...] = s_in_ref[...]

    n_valid = lens_ref[b] - c * chunk       # of this chunk's positions, left-aligned

    @pl.when(n_valid > 0)
    def _chunk():
        _, p, n = s_ref.shape[2:]
        bm, cm = b_ref[0, 0], c_ref[0, 0]                                 # [T, N] each
        s = s_ref[0, 0].reshape(heads * p, n)                             # the block's states, stacked
        read = dot3(s, cm, ((1,), (1,)))                                  # (C S_0^T)^T: [heads x P, T]
        # (C B^T)^T: [j, i] = C_i . B_j, the source position on the sublanes
        bct = jax.lax.dot_general(bm, cm, (((1,), (1,)), ((), ())), precision=HIGHEST,
                                  preferred_element_type=jnp.float32)
        j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        grow, drow = grow_ref[0, 0], drow_ref[0, 0]                        # [heads, T] each
        last = gcol_ref[0, 0, :, chunk - 1:chunk, :]                      # [heads, 1, 1]: each head's whole log decay
        for h in range(heads):
            g, dt = grow[h:h + 1], drow[h:h + 1]                          # [1, T] each
            xt = x_ref[0, h, 0]                                           # [P, T]
            # e^{gamma_i - gamma_j} for j <= i (the difference is <= 0 there), else 0
            decay = jnp.where(j <= i, jnp.exp(jnp.where(j <= i, g - gcol_ref[0, 0, h], 0.0)), 0.0)
            y_ref[0, h, 0] = jnp.exp(g) * read[h * p:(h + 1) * p] + jnp.dot(
                dt * xt, decay * bct, precision=HIGHEST, preferred_element_type=jnp.float32)
            w_ref[h * p:(h + 1) * p] = jnp.exp(last[h] - g) * dt * xt    # the inputs, decayed to the chunk's end
        update = dot3(w_ref[...], bm, ((1,), (0,)))                       # [heads x P, N]
        for h in range(heads):
            # (a [1, 1] is not broadcast both ways at once: along the lanes first)
            total = jnp.exp(last[h] + jnp.zeros((1, n), jnp.float32))
            s_ref[0, 0, h] = total * s[h * p:(h + 1) * p] + update[h * p:(h + 1) * p]

    @pl.when(n_valid <= 0)
    def _no_valid_position():
        y_ref[...] = jnp.zeros_like(y_ref)


def _head_block(heads: int) -> int:
    """The most heads up to HEAD_BLOCK that divide the head count."""
    return next(n for n in range(min(HEAD_BLOCK, heads), 0, -1) if heads % n == 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_scan(
    x: jax.Array,        # [B, H, n, T, P]
    dt: jax.Array,       # [B, H, n, T]: the step, 0 where a position is not valid
    gamma: jax.Array,    # [B, H, n, T]: dt A summed from the chunk's start
    b: jax.Array,        # [B, n, T, N], shared by every head
    c: jax.Array,        # [B, n, T, N]
    lens: jax.Array,     # [B] valid positions of each row, left-aligned
    state: jax.Array,    # [periods, B, H, P, N]
    period: jax.Array | int = 0,  # which of the entries these rows' state is
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(Y [B, H, n, T, P] without the skip, `state` with entry `period`
    advanced over the n chunks; its other entries as they were). All
    float32. Y is zero in a chunk that holds no valid position of its
    row."""
    interpret = pallas_interpret(interpret)
    B, H, n, T, P = x.shape
    N = b.shape[-1]
    assert state.shape[1:] == (B, H, P, N) and dt.shape == gamma.shape == (B, H, n, T), (
        x.shape, b.shape, dt.shape, state.shape)
    hb = _head_block(H)
    period = jnp.asarray(period, jnp.int32).reshape(1)
    grow, drow = jnp.moveaxis(gamma, 2, 1), jnp.moveaxis(dt, 2, 1)   # [B, n, H, T]: a head's positions as a row

    def per_chunk(*tail):
        return pl.BlockSpec((1, 1, hb, *tail), lambda r, h, k, p, l: (r, k, h, *(0,) * len(tail)))

    shared = pl.BlockSpec((1, 1, T, N), lambda r, h, k, p, l: (r, k, 0, 0))
    x_spec = pl.BlockSpec((1, hb, 1, P, T), lambda r, h, k, p, l: (r, h, k, 0, 0))
    s_spec = pl.BlockSpec((1, 1, hb, P, N), lambda r, h, k, p, l: (p[0], r, h, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_kernel, heads=hb, chunk=T),
        name="ssd_chunk_scan",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb, n),
            in_specs=[per_chunk(T), per_chunk(T, 1), per_chunk(T), x_spec, shared, shared, s_spec],
            out_specs=[x_spec, s_spec],
            scratch_shapes=[pltpu.VMEM((hb * P, T), jnp.float32)],
        ),
        # the state stays in HBM on both sides of the call, as ops/gdn_scan.py's
        out_shape=[jax.ShapeDtypeStruct((B, H, n, P, T), jnp.float32), pltpu.HBM(state.shape, jnp.float32)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(period, lens.astype(jnp.int32), grow, grow[..., None], drow, jnp.swapaxes(x, -1, -2), b, c, state)
    return jnp.swapaxes(y, -1, -2), state
