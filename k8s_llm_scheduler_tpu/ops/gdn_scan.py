"""The chunked gated delta rule as one Pallas kernel: a (row, block of value
heads)'s state is read from HBM once, kept in VMEM across the call's chunks,
and written once, where it lies; everything a chunk needs beside it is made
in VMEM from q, k, v, the decay and beta as they are.

For each value head with state S [dk, dv], token by token: `S <- e^{g_t}
S`; `delta = beta_t (v_t - S^T k_t)`; `S <- S + k_t delta^T`; `o_t = S^T
q_t` (models/gdn_moe.py's layer equations). Over a chunk of C positions,
with gamma_t = sum_{i <= t} g_i and the state S_0 the chunk starts from, the
deltas obey
    delta_i + beta_i sum_{j < i} e^{gamma_i - gamma_j} (k_i . k_j) delta_j
        = beta_i (v_i - e^{gamma_i} S_0^T k_i),
a unit-lower-triangular system (I + A) Delta = rhs that is linear in S_0, so
it is solved for both of its parts, U = (I + A)^-1 (beta v) and W = (I +
A)^-1 (beta e^gamma k), and then, chunk after chunk,
    Delta = U - W S_0
    O     = (e^gamma q) S_0 + (tril(e^{gamma_i - gamma_j}) * q k^T) Delta
    S_C   = e^{gamma_C} S_0 + (e^{gamma_C - gamma} k)^T Delta.

- grid (rows, value heads / block, chunks), the chunk axis last and
  sequential. The state's output block has the same index for every chunk
  of a (row, head block), so it stays in VMEM from the first chunk, which
  copies the input block into it, to the last, after which it is written
  back: one read and one write of the state a call, whatever the number of
  chunks (1 in block decode, 4 in the suffix call, 64 in a prefix prefill).
  A chunk that holds no valid position of its row (`lens`) is passed over:
  it would leave the state as it was, and its O is zero;
- q and k come a key head once for the value heads it serves (the block's
  index map gives value heads h the key head h // rep), gamma and beta a
  head's positions as a column (and gamma as a row too: it meets the
  chunk's positions both ways). k k^T and q k^T are one product each a key
  head; the decays, A, the right-hand sides and the scaled copies of q and
  k are elementwise in VMEM. Nothing a chunk computes is written to HBM but
  O. Inside a grid step a LOOP runs over the block's key heads, its body
  written once: with the block's eight heads written out side by side the
  kernel is 6% faster end to end and every process that loads its programs
  pays 5-7 s more of set-up, which the cell's bound does not hold (PERF.md
  §6 PR 38);
- THE SOLVE IS FORWARD SUBSTITUTION, a column of A at a time: `X <- X -
  A[:, j] X[j, :]` for j = 0 .. C - 2 on X = [beta v | beta e^gamma k] [C,
  dv + dk] leaves X = [U | W], exact to rounding where equal keys make a
  series of A's powers grow before it cancels (a prompt that repeats
  itself: tests/test_gdn_moe.py holds the case). The columns go in groups
  of SWEEP, and a group at or past the row's last valid position is passed
  over: below that position beta = 0 and A's rows are zero, so the group
  would subtract nothing. A decode block of 24 holds one to three valid
  positions a row most of the time: one group or none;
- THE STATE IS A WHOLE MEMBER [periods, rows, Hv, dk, dv] of what a sequence
  carries and `period` says which entry to advance: the index is a
  prefetched scalar read by the state's index maps (as ops/grouped_matmul.py
  reads the expert stack at a prefetched layer), and the output is ALIASED
  to the input, so the layer scan carries the members and each layer
  updates its entry in place; the other entries are never touched. Where
  the caller still needs the array it handed in (a jit argument that is not
  donated: a pin's state), XLA copies it first: the kernel never writes
  what its caller can still see;
- the arithmetic is the XLA program's that it replaced (PR 37), the solve
  apart: float32 throughout; the two products with the state in THREE
  bfloat16 passes (operands split by hand into a high and a low half, hi hi
  + lo hi + hi lo: Mosaic offers one pass or six; on the chip the error
  against the token-by-token recurrence read the same to two digits at
  three passes and at six, and 40 x larger at one, PERF.md §6 PR 37); k
  k^T, q k^T and its product with Delta at `highest`. A position that is
  not valid has g = 0 and beta = 0: zero rows in U and W, a decay of one, so
  a row with no valid position gets its state back bit for bit.

Equivalence against the token-by-token recurrence: tests/test_gdn_moe.py
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

# Value heads a grid step holds: 8 x [128, 128] float32 = 512 KB of state,
# so a layer of 8 rows x 32 heads is 32 steps, each long enough to hide the
# next step's copy behind its products.
HEAD_BLOCK = 8
# Columns of the solve that go (or are passed over) together.
SWEEP = 8


def _kernel(_period_ref, lens_ref, gcol_ref, grow_ref, bcol_ref, q_ref, k_ref, v_ref, s_in_ref, o_ref, s_ref,
            x_ref, *, heads: int, rep: int, chunk: int):
    b, c = pl.program_id(0), pl.program_id(2)

    @pl.when(c == 0)
    def _first_chunk():
        s_ref[...] = s_in_ref[...]

    n_valid = lens_ref[b] - c * chunk       # of this chunk's positions, left-aligned
    dv = o_ref.shape[-1]
    nt = (((1,), (1,)), ((), ()))

    def key_head(kh, _):
        """A key head's chunk and the `rep` value heads it serves."""
        i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        q, k = q_ref[0, kh, 0], k_ref[0, kh, 0]                           # [C, dk]
        qkt = jax.lax.dot_general(q, k, nt, precision=HIGHEST, preferred_element_type=jnp.float32)
        kkt = jax.lax.dot_general(k, k, nt, precision=HIGHEST, preferred_element_type=jnp.float32)
        heads_here = [kh * rep + r for r in range(rep)]
        gammas = [gcol_ref[0, 0, 0, h] for h in heads_here]               # [C, 1] each
        # e^{gamma_i - gamma_j} for j <= i (the difference is <= 0 there), else 0
        decays = [jnp.where(i >= j, jnp.exp(jnp.where(i >= j, gamma - grow_ref[0, 0, 0, h], 0.0)), 0.0)
                  for h, gamma in zip(heads_here, gammas)]
        a = []
        for r, (h, gamma, decay) in enumerate(zip(heads_here, gammas, decays)):
            beta = bcol_ref[0, 0, 0, h]
            a.append(jnp.where(i > j, beta * decay * kkt, 0.0))
            x_ref[r] = jnp.concatenate([beta * v_ref[0, h, 0], (beta * jnp.exp(gamma)) * k], axis=1)
        for first in range(0, chunk - 1, SWEEP):
            @pl.when(first < n_valid - 1)
            def _columns():
                for r in range(rep):
                    x = x_ref[r]
                    for col in range(first, min(first + SWEEP, chunk - 1)):
                        x = x - a[r][:, col:col + 1] * x[col:col + 1, :]
                    x_ref[r] = x
        for r, (h, gamma, decay) in enumerate(zip(heads_here, gammas, decays)):
            last = gamma[chunk - 1:chunk, :]                              # [1, 1]: the chunk's whole log decay
            u, w = x_ref[r, :, :dv], x_ref[r, :, dv:]
            s = s_ref[0, 0, h]
            # what reads S_0, stacked: one product with the state for both
            read = dot3(jnp.concatenate([w, q * jnp.exp(gamma)], axis=0), s, ((1,), (0,)))
            delta = u - read[:chunk]
            o_ref[0, h, 0] = read[chunk:] + jnp.dot(qkt * decay, delta, precision=HIGHEST,
                                                    preferred_element_type=jnp.float32)
            # (a [1, 1] is not broadcast both ways at once: along the lanes first)
            total = jnp.exp(last + jnp.zeros((1, dv), jnp.float32))
            s_ref[0, 0, h] = total * s + dot3(k * jnp.exp(last - gamma), delta, ((0,), (0,)))
        return 0

    @pl.when(n_valid > 0)
    def _chunk():
        jax.lax.fori_loop(0, heads // rep, key_head, 0)

    @pl.when(n_valid <= 0)
    def _no_valid_position():
        o_ref[...] = jnp.zeros_like(o_ref)


def _head_block(heads: int, rep: int) -> int:
    """The most value heads up to HEAD_BLOCK that divide the head count and
    hold whole groups of the `rep` value heads a key head serves."""
    return next(n for n in range(min(HEAD_BLOCK, heads), 0, -1) if heads % n == 0 and n % rep == 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_chunk_scan(
    q: jax.Array,        # [B, Hk, n, C, dk] normalised and scaled
    k: jax.Array,        # [B, Hk, n, C, dk] normalised
    v: jax.Array,        # [B, H, n, C, dv]
    gamma: jax.Array,    # [B, H, n, C]: the log decay summed from the chunk's start
    beta: jax.Array,     # [B, H, n, C]
    lens: jax.Array,     # [B] valid positions of each row, left-aligned
    state: jax.Array,    # [P, B, H, dk, dv]
    period: jax.Array | int = 0,  # which of the P entries these rows' state is
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(O [B, H, n, C, dv], `state` with entry `period` advanced over the n
    chunks; its other entries as they were). All float32. Value head h reads
    key head h // (H / Hk); a position that is not valid has gamma's step and
    beta zero. O is zero in a chunk that holds no valid position of its
    row."""
    interpret = pallas_interpret(interpret)
    B, Hk, n, C, dk = k.shape
    H, dv = v.shape[1], v.shape[-1]
    assert state.shape[1:] == (B, H, dk, dv) and gamma.shape == beta.shape == (B, H, n, C), (
        k.shape, v.shape, gamma.shape, state.shape)
    rep = H // Hk
    hb = _head_block(H, rep)
    period = jnp.asarray(period, jnp.int32).reshape(1)

    def per_head(x, shape):  # [B, H, n, C] -> [B, H / hb, n, hb, *shape]: a head's positions as a column or a row
        return jnp.moveaxis(x.reshape(B, H // hb, hb, n, *shape), 2, 3)

    gcol, grow, bcol = per_head(gamma, (C, 1)), per_head(gamma, (1, C)), per_head(beta, (C, 1))

    def chunked(heads, *tail):
        return pl.BlockSpec((1, heads, 1, *tail), lambda b, h, c, p, l: (b, h, c, *(0,) * len(tail)))

    s_spec = pl.BlockSpec((1, 1, hb, dk, dv), lambda b, h, c, p, l: (p[0], b, h, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, heads=hb, rep=rep, chunk=C),
        name="gdn_chunk_scan",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb, n),
            in_specs=[chunked(1, hb, C, 1), chunked(1, hb, 1, C), chunked(1, hb, C, 1), chunked(hb // rep, C, dk),
                      chunked(hb // rep, C, dk), chunked(hb, C, dv), s_spec],
            out_specs=[chunked(hb, C, dv), s_spec],
            scratch_shapes=[pltpu.VMEM((rep, C, dv + dk), jnp.float32)],  # X of the solve, a value head each
        ),
        # the state stays in HBM on both sides of the call (left to itself the compiler stages a whole
        # member through its fast memory around every call: three copies of it a layer)
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.float32), pltpu.HBM(state.shape, jnp.float32)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(period, lens.astype(jnp.int32), gcol, grow, bcol, q, k, v, state)
    return o, state
