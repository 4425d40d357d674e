"""Paged KV cache with static shapes — the on-device replacement for the
reference's client-side RequestCache.

The reference caches whole *decisions* in host RAM (reference
scheduler.py:257-294); the TPU build additionally needs token-level KV state
for in-flight generations. vLLM-style paging under JAX's static-shape
regime (SURVEY §7 hard part #2):

- K/V arrays are [n_layers, num_pages, page_size, n_kv_heads, head_dim],
  allocated once; page 0 is reserved scratch (inactive decode slots write
  there; padded prefill pages point there).
- A fixed pool of `max_slots` sequence slots; per-slot page tables
  [max_slots, max_pages_per_seq] map logical token blocks to pages.
- Page allocation/free is HOST-side bookkeeping (a free list) between jit
  calls; all device-side mutation happens inside jit'd scatters with
  donated buffers, so shapes never change and nothing recompiles.

Prefix reuse lives OUTSIDE this cache: the burst-shared cluster-state block
is prefilled once into a dense [L, Sp, n_kv, hd] buffer (engine/engine.py
_PrefixKV) and attended via cascade attention (ops/attention.py), so slot
pages hold only each request's suffix + generated tokens. That keeps page
tables narrow — the decode gather reads a few pages per slot instead of the
whole prompt.

write_prefill / ensure_capacity / note_token_appended remain as the manual
page-management API for driving forward_decode directly (tests, external
callers); the engine reserves full capacity at admission and scatters KV
inside its jit programs instead.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from k8s_llm_scheduler_tpu.models.configs import LlamaConfig


class OutOfPagesError(RuntimeError):
    """The page pool is exhausted — caller should backpressure admissions."""


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_pages(cache: jax.Array, page_ids: jax.Array, blocks: jax.Array) -> jax.Array:
    """cache[:, page_ids[i]] = blocks[:, i] for all i (donated, in-place)."""
    return cache.at[:, page_ids].set(blocks)


@dataclasses.dataclass
class SlotInfo:
    slot: int
    length: int  # tokens currently stored
    pages: list[int]  # owned pages (refcounted globally)


class PagedKVCache:
    def __init__(
        self,
        cfg: LlamaConfig,
        num_pages: int = 256,
        page_size: int = 128,
        max_slots: int = 8,
        max_pages_per_seq: int = 64,
        dtype=None,
        sharding=None,  # jax.sharding.NamedSharding | None — kv-head spec
    ) -> None:
        self.cfg = cfg
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_slots = int(max_slots)
        self.max_pages_per_seq = int(max_pages_per_seq)
        dtype = dtype or cfg.dtype
        from k8s_llm_scheduler_tpu.models import family

        # a page holds the model's per-token cache: (k, v) [n_kv, hd] for
        # the dense family (a model without paged forwards is given one
        # scratch page by the engine)
        model = family(cfg)
        k_tok, v_tok = model.cache_token_shapes(cfg)
        # On a tp mesh the pages are BORN head-sharded (parallel/sharding.
        # kv_cache_spec via engine/sharded): each chip holds n_kv/tp heads
        # of every page, so KV capacity scales with the group instead of
        # replicating. The jitted mutators donate k/v, and donated outputs
        # keep their input sharding, so placement here is placement for
        # the cache's whole life — the host free-list/page-table
        # bookkeeping below never looks at device layout and is unchanged.
        # `device=sharding` creates the shards in place: device_put of a
        # jnp.zeros would first build the WHOLE pool on device 0 (8.6 GB of
        # k+v at 8B next to that chip's 4 GB of weights).
        self.sharding = sharding
        pool = (model.cache_layers(cfg), num_pages, page_size)
        self.k = jnp.zeros(pool + k_tok, dtype=dtype, device=sharding)
        self.v = jnp.zeros(pool + v_tok, dtype=dtype, device=sharding)
        # Host-side state. Page 0 is scratch — never allocated.
        self._free = list(range(num_pages - 1, 0, -1))
        self._refcount = np.zeros(num_pages, dtype=np.int32)
        self._slots: dict[int, SlotInfo] = {}
        self._free_slots = list(range(max_slots - 1, -1, -1))
        # Device mirrors (rebuilt on change; [max_slots, max_pages_per_seq]).
        self._tables_np = np.zeros((max_slots, max_pages_per_seq), dtype=np.int32)
        self._tables_dirty = True
        self._tables_dev: jax.Array | None = None

    # ------------------------------------------------------------- plumbing
    @property
    def pages_free(self) -> int:
        return len(self._free)

    def page_tables(self) -> jax.Array:
        if self._tables_dirty or self._tables_dev is None:
            self._tables_dev = jnp.asarray(self._tables_np)
            self._tables_dirty = False
        return self._tables_dev

    def _alloc_pages(self, n: int) -> list[int]:
        if n > len(self._free):
            raise OutOfPagesError(f"need {n} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refcount[p] += 1
        return pages

    def _release_pages(self, pages: list[int]) -> None:
        for p in pages:
            self._refcount[p] -= 1
            if self._refcount[p] == 0:
                self._free.append(p)

    def pages_needed(self, n_tokens: int) -> int:
        return max(1, -(-n_tokens // self.page_size))

    # ----------------------------------------------------------------- slots
    def allocate_slot(self, n_tokens: int, reserve_decode: int = 0) -> int:
        """Claim a slot with pages covering n_tokens (+reserve_decode more)."""
        if not self._free_slots:
            raise OutOfPagesError("no free sequence slots")
        need = self.pages_needed(n_tokens + reserve_decode)
        if need > self.max_pages_per_seq:
            raise OutOfPagesError(
                f"sequence needs {need} pages > max_pages_per_seq={self.max_pages_per_seq}"
            )
        pages = self._alloc_pages(need)
        slot = self._free_slots.pop()
        self._slots[slot] = SlotInfo(slot=slot, length=0, pages=pages)
        row = np.zeros(self.max_pages_per_seq, dtype=np.int32)
        row[: len(pages)] = pages
        self._tables_np[slot] = row
        self._tables_dirty = True
        return slot

    def free_slot(self, slot: int) -> None:
        info = self._slots.pop(slot)
        self._release_pages(info.pages)
        self._free_slots.append(slot)
        self._tables_np[slot] = 0
        self._tables_dirty = True

    def slot_length(self, slot: int) -> int:
        return self._slots[slot].length

    def slot_pages(self, slot: int) -> list[int]:
        """The slot's owned page ids, in logical-block order."""
        return list(self._slots[slot].pages)

    def ensure_decode_capacity(self, slot: int) -> None:
        """Grow the slot by one page if the next token would overflow."""
        self.ensure_capacity(slot, self._slots[slot].length + 1)

    def ensure_capacity(self, slot: int, upto_len: int) -> None:
        """Grow the slot's page list to cover `upto_len` tokens (used to
        reserve a whole fused-decode chunk ahead of time)."""
        info = self._slots[slot]
        while len(info.pages) * self.page_size < upto_len:
            if len(info.pages) + 1 > self.max_pages_per_seq:
                raise OutOfPagesError("sequence exceeded max_pages_per_seq")
            (page,) = self._alloc_pages(1)
            self._tables_np[info.slot, len(info.pages)] = page
            info.pages.append(page)
            self._tables_dirty = True

    def note_token_appended(self, slot: int) -> None:
        self._slots[slot].length += 1

    def truncate(self, slot: int, new_length: int) -> None:
        """Shrink a slot to `new_length` tokens, freeing the tail pages.

        The paged-KV rollback op for speculative decoding (spec/decoder.py):
        rejected draft tokens wrote K/V into the slot's tail pages, and the
        whole tail beyond the accepted prefix unwinds by releasing exactly
        the pages no longer needed to cover `new_length` tokens. Freed pages
        return to the pool (refcounted — never double-freed) and a
        subsequent ensure_capacity/allocate reuses them. Device-side page
        contents are NOT cleared: stale K/V past `new_length` is never
        attended because every reader masks by valid length, and the next
        append overwrites it. A slot always keeps >= 1 page (matching
        allocate_slot). Idempotent at the same `new_length`.

        Contract: PAGES only ever shrink here (truncate never allocates),
        but the slot's RECORDED length is SET to `new_length` (clamped to
        page capacity) — callers own the invariant that `new_length` never
        exceeds the tokens actually written, or slot_length() would report
        uninitialized positions as valid. The engine-driven spec path
        tracks its own host-side count and satisfies this by construction;
        manual-API callers (write_prefill/note_token_appended) must only
        ever truncate downward from their written length.
        """
        if new_length < 0:
            raise ValueError(f"new_length must be >= 0, got {new_length}")
        info = self._slots[slot]
        keep = self.pages_needed(new_length)
        if keep < len(info.pages):
            dropped = info.pages[keep:]
            del info.pages[keep:]
            self._release_pages(dropped)
            self._tables_np[slot, keep:] = 0
            self._tables_dirty = True
        info.length = min(new_length, len(info.pages) * self.page_size)

    # --------------------------------------------------------------- prefill
    def write_prefill(
        self,
        slot: int,
        k_all: jax.Array,  # [L, S, n_kv, hd] — one sequence's prefill KV
        v_all: jax.Array,
        seq_len: int,
    ) -> None:
        """Scatter a sequence's prefill K/V into its pages.

        S (the padded bucket length) may exceed seq_len; whole pages beyond
        the needed count are routed to scratch page 0.
        """
        info = self._slots[slot]
        L, S, n_kv, hd = k_all.shape
        assert S % self.page_size == 0, "bucket sizes must be multiples of page_size"
        n_blocks = S // self.page_size
        used = self.pages_needed(seq_len)
        # Destination for each block: real page while within the sequence,
        # scratch page 0 for pure-padding blocks.
        dest = np.zeros(n_blocks, dtype=np.int32)
        for i in range(min(used, n_blocks)):
            dest[i] = info.pages[i]
        page_ids = jnp.asarray(dest)
        blocks_k = k_all.reshape(L, n_blocks, self.page_size, n_kv, hd)
        blocks_v = v_all.reshape(L, n_blocks, self.page_size, n_kv, hd)
        self.k = _scatter_pages(self.k, page_ids, blocks_k)
        self.v = _scatter_pages(self.v, page_ids, blocks_v)
        info.length = seq_len

