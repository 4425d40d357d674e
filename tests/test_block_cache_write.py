"""ops/attention.write_block: a decode block goes into the wave's
generated-token cache as one window a row. Held against the per-token
scatter it replaced (PR 31), bit for bit wherever a later call can look,
for the dense family's (k, v) tokens and the latent family's (c_kv, k_r);
and the lowered forwards of both families hold no scatter under
`kv_writeback`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_scheduler_tpu.models import llama
from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
from k8s_llm_scheduler_tpu.ops.attention import write_block

L, CAP = 3, 40
TOKENS = {"dense_kv": (2, 16), "latent_c": (32,), "latent_rope": (8,)}


def scatter_block(buf, tail, block, blk_valid):
    """What both forwards did before PR 31, in every layer: valid token j of
    row r to slot tail[r] + j, a padded position to the trash slot (the
    last one) of a buffer cap + 1 long."""
    R, F = blk_valid.shape
    dest = jnp.where(blk_valid, tail[:, None] + jnp.arange(F)[None, :], buf.shape[2] - 1)
    row = jnp.arange(R)[:, None]
    for layer in range(buf.shape[0]):
        buf = buf.at[layer, row, dest].set(block[layer].astype(buf.dtype))
    return buf


def edge_rows(rng, R, F, case):
    """Random (tail, blk_len) with one row set to the case's edge."""
    blk_len = rng.integers(0, F + 1, R)
    tail = np.asarray([rng.integers(0, CAP - n + 1) for n in blk_len])
    r = case % R
    if case == 0:
        blk_len[r] = 0
    elif case == 1:
        blk_len[r], tail[r] = F, rng.integers(0, CAP - F + 1)
    elif case == 2:
        tail[r] = 0
    else:
        tail[r] = CAP - blk_len[r]  # the block ends at the last slot
    return tail.astype(np.int32), blk_len.astype(np.int32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("F", [1, 24])
@pytest.mark.parametrize("R", [1, 4, 8])
@pytest.mark.parametrize("token", TOKENS)
def test_every_exposed_slot_equals_the_scatter(token, R, F, dtype):
    shape = TOKENS[token]
    rng = np.random.default_rng(R * 100 + F)
    for case in range(4):  # blk_len 0, blk_len F, tail 0, tail + blk_len = cap
        tail, blk_len = edge_rows(rng, R, F, case)
        blk_valid = np.arange(F)[None, :] < blk_len[:, None]
        old = rng.normal(size=(L, R, CAP, *shape))
        block = jnp.asarray(rng.normal(size=(L, R, F, *shape)), jnp.float32)
        pad = lambda n: jnp.asarray(  # noqa: E731
            np.concatenate([old, np.zeros((L, R, n, *shape))], axis=2), dtype)
        want = scatter_block(pad(1), jnp.asarray(tail), block, jnp.asarray(blk_valid))
        got = write_block(pad(F), jnp.asarray(tail), block)
        assert got.shape == (L, R, CAP + F, *shape) and got.dtype == dtype
        got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
        for r in range(R):
            hi = tail[r] + blk_len[r]
            np.testing.assert_array_equal(
                got[:, r, :hi], want[:, r, :hi],
                err_msg=f"case {case} row {r} tail {tail[r]} len {blk_len[r]}",
            )


PAD = -7.0  # what the padded positions of a block hold; valid tokens are > 0


def _four_blocks(slots):
    """Four blocks in a row (F 8, capacity 30: two rows end at it) through
    `write_block` into a buffer `slots` long. Returns (buffer, tail, the
    valid tokens each row was handed, in order)."""
    rng = np.random.default_rng(7)
    lens = np.asarray([[8, 8, 8, 6], [8, 7, 8, 7], [3, 8, 8, 8], [0, 5, 8, 8]])
    R, F = len(lens), 8
    buf = jnp.zeros((L, R, slots, 4), jnp.float32)
    tail = np.zeros(R, np.int32)
    served = [[] for _ in range(R)]
    for i in range(lens.shape[1]):
        blk_len = lens[:, i]
        block = np.full((L, R, F, 4), PAD, np.float32)
        for r in range(R):
            vals = rng.uniform(1.0, 2.0, size=(L, blk_len[r], 4)).astype(np.float32)
            block[:, r, :blk_len[r]] = vals
            served[r].append(vals)
        buf = write_block(buf, jnp.asarray(tail), jnp.asarray(block))
        tail = tail + blk_len
    return np.asarray(buf), tail, [np.concatenate(s, axis=1) for s in served]


def test_nothing_of_a_padded_position_is_ever_under_a_tail():
    buf, tail, served = _four_blocks(slots=30 + 8)
    for r in range(len(tail)):
        assert (buf[:, r, :tail[r]] != PAD).all(), r
        np.testing.assert_array_equal(buf[:, r, :tail[r]], served[r])


def test_a_window_clamped_by_a_shorter_buffer_is_what_the_check_above_catches():
    """The fault the cap + F length exists for: in a buffer one slot longer
    than the capacity (the old trash slot), dynamic_update_slice moves a
    window that would overrun back inside, onto tokens already written."""
    buf, tail, served = _four_blocks(slots=30 + 1)
    spoiled = [r for r in range(len(tail))
               if (buf[:, r, :tail[r]] == PAD).any()
               or not np.array_equal(buf[:, r, :tail[r]], served[r])]
    assert spoiled, "a clamped window went unnoticed"


TINY = LlamaConfig(
    name="block-cache-test", vocab_size=256, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=2, d_ff=128, max_seq_len=512, rope_theta=10000.0,
    dtype=jnp.float32, tie_embeddings=True,
)


@pytest.mark.parametrize("ragged", [False, True], ids=["dense", "ragged"])
def test_the_lowered_dense_forward_writes_windows_and_no_scatter(ragged):
    """`kv_writeback` still names the write (the benchmark's
    kv_writeback_device_ms_per_bind reads that scope), and what it names
    are dynamic_update_slices. tests/test_mla_moe.py holds the same for
    the latent family."""
    R, F, Ss, Sp, cap = 2, 4, 8, 16, 12
    kv, hd, Ln = TINY.n_kv_heads, TINY.head_dim, TINY.n_layers
    params = jax.eval_shape(lambda k: llama.init_params(k, TINY), jax.random.PRNGKey(0))
    z = lambda *shape: jax.ShapeDtypeStruct(shape, TINY.dtype)  # noqa: E731
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    text = jax.jit(llama.forward_block_decode, static_argnums=1, static_argnames="ragged").lower(
        params, TINY, i32(R, F), jax.ShapeDtypeStruct((R, F), bool), i32(R), i32(R, F),
        z(Ln, R, Ss, kv, hd), z(Ln, R, Ss, kv, hd), i32(R),
        z(Ln, R, cap + F, kv, hd), z(Ln, R, cap + F, kv, hd), i32(R),
        z(Ln, Sp, kv, hd), z(Ln, Sp, kv, hd), i32(), ragged=ragged,
    ).as_text(debug_info=True)
    assert "kv_writeback/dynamic_update_slice" in text
    assert "kv_writeback/scatter" not in text
    for path in ("attn/", "mlp/", "lm_head/", "embed/"):
        assert path in text, path
