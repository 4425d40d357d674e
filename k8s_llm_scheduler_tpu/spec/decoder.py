"""SpeculativeDecoder: asynchronous draft-ahead / verify-behind pipeline.

The serial shape this module replaced ran propose -> verify -> blocking
fetch per round, and held the whole fused runtime off while a request was
open (`engine.fused_hold`). The rebuilt decoder is a ROUND STATE MACHINE
over the engine's general paged-decode state that composes with — instead
of excluding — the fused runtime:

- **Pipelined dispatch, one sync per round** (*SwiftSpec*, PAPERS.md).
  Each round enqueues target-verify for block K and the draft's AHEAD
  proposal for block K+1 back-to-back, then fetches once. The ahead
  proposal anchors on the draft's own guess at the round's bonus token
  (spec/draft.py returns the K+1-th sample instead of discarding it), so
  when the verify fully accepts and the bonus matches the guess — every
  steady-state round for a well-matched draft, ALWAYS for a greedy
  self-draft — the next round's block is already device-resident and the
  draft ran entirely in the shadow of the verify sync. A miss discards
  the ahead block (the dense draft buffer re-proposes from the corrected
  token; stale entries are never attended — position-masked) and costs
  exactly the old serial round.
- **Fused-runtime coexistence**. A speculative request's slot is marked
  `external` and deactivated in the engine's decode batch at start():
  fused chunks for OTHER slots dispatch freely between (and during)
  spec rounds — everything rides one device queue in dispatch order —
  and `engine.fused_hold` is GONE. The auto-disable hand-off re-arms the
  slot and finishes through `engine.step_fused`, so a disabled request
  rides the fused runtime instead of the slow chunked path.
- **Dense-table grammar** (engine/fused/tables.py). Greedy constrained
  verification masks and transitions through the SAME dense
  transition-table the fused while_loop gathers from; sampling mode and
  cap-exceeded grammars keep the sparse K-space tables (spec/verify.py).
- **Draft-free hidden-transfer arm** (*Hidden Transfer*, PAPERS.md;
  spec/hidden.py). `arm="hidden"` drops the draft model: proposals come
  from transfer heads applied to the target's own hidden state INSIDE
  the verify program, so each round is ONE dispatch + one fetch and the
  proposal block rides device-resident between rounds.

Robustness is unchanged in kind, upgraded in destination: the per-request
acceptance EWMA still auto-disables a draft that stops earning its keep,
but the mid-stream hand-off now lands on the fused decode path; the
grammar-safe `PagedKVCache.truncate` rollback still absorbs every
mis-speculated tail; and `on_swap` (called by engine.swap_params) rolls
back any open speculative block before new weights install. Per-request
round telemetry fences into the profiler's SPEC_SEGMENTS books
(observability/profiler.py: draft/verify/rollback/unattributed, sum ==
wall) with the measured draft/verify overlap fraction beside them.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
from k8s_llm_scheduler_tpu.models.llama import Params, init_hidden_transfer
from k8s_llm_scheduler_tpu.observability.trace import recorder
from k8s_llm_scheduler_tpu.spec.draft import DraftRunner
from k8s_llm_scheduler_tpu.spec.verify import _verify_impl


@dataclasses.dataclass
class SpecStats:
    requests: int = 0
    rounds: int = 0
    proposed: int = 0
    accepted: int = 0
    emitted: int = 0
    disables: int = 0
    fallback_requests: int = 0
    unsupported_requests: int = 0
    # Async-pipeline books: rounds whose proposal block was already
    # device-resident when the round began (the draft ran in the shadow
    # of the previous verify), ahead proposals discarded on a miss, and
    # open-block rollbacks forced by a weight swap.
    overlapped_rounds: int = 0
    ahead_wasted: int = 0
    swap_rollbacks: int = 0

    def snapshot(self) -> dict[str, Any]:
        out = dataclasses.asdict(self)
        out["acceptance_rate"] = (
            self.accepted / self.proposed if self.proposed else 0.0
        )
        out["tokens_per_round"] = (
            self.emitted / self.rounds if self.rounds else 0.0
        )
        out["overlap_fraction"] = (
            self.overlapped_rounds / self.rounds if self.rounds else 0.0
        )
        return out


@dataclasses.dataclass
class _Proposal:
    """A draft proposal block, fully device-resident (draft arm).

    `anchor_tok`/`anchor_st` are the token the block continues from and
    the DFA state after it (device scalars — an ahead proposal's anchor
    is the previous block's guess, never fetched). `toks`/`states` are
    the [K+1] proposal chain (index K = the draft's guess at the round's
    bonus token); `idxs`/`logits` feed the rejection sampler."""

    anchor_tok: jax.Array
    anchor_st: jax.Array
    pos: int  # anchor's absolute position (host bookkeeping)
    toks: jax.Array
    states: jax.Array
    idxs: jax.Array
    logits: jax.Array


@dataclasses.dataclass
class _HiddenBlock:
    """The hidden arm's next proposal block: produced inside the previous
    round's verify program, host copies fetched in that round's single
    sync (the emit path needs token values without a second fetch)."""

    pos: int  # anchor's absolute position
    toks: jax.Array
    states: jax.Array
    idxs: jax.Array
    logits: jax.Array
    toks_np: np.ndarray
    states_np: np.ndarray


@dataclasses.dataclass
class _Stream:
    """One speculative request mid-flight (the round state machine)."""

    req_id: int
    slot: int
    n_prompt: int
    max_new: int
    hard_cap: int
    generated: list[int]
    t_cur: int
    st_cur: int
    n_own: int
    finished: bool = False
    disabled: bool = False
    # Set when the auto-disable edge handed the slot back to the engine:
    # the request is a NORMAL engine request from then on and its
    # Finished record arrives through the caller's own
    # step_fused()/decode_fused() harvest, never through advance().
    handed_off: bool = False
    ewma: float | None = None
    rounds: int = 0
    pending: Any = None  # _Proposal | _HiddenBlock | None
    t0: float = dataclasses.field(default_factory=time.perf_counter)
    seg: dict[str, float] = dataclasses.field(
        default_factory=lambda: {"draft": 0.0, "verify": 0.0, "rollback": 0.0}
    )
    overlapped: int = 0


class SpeculativeDecoder:
    """Speculative decoding over one engine + one draft arm."""

    def __init__(
        self,
        engine,  # InferenceEngine (not annotated: avoids an import cycle)
        draft_params: Params | None = None,
        draft_cfg: LlamaConfig | None = None,
        *,
        k: int = 4,
        arm: str = "draft",
        hidden_head: Params | None = None,
        hidden_seed: int = 0,
        disable_threshold: float = 0.3,
        ewma_alpha: float = 0.3,
        min_rounds: int = 4,
    ) -> None:
        if k < 1:
            raise ValueError(f"spec_k must be >= 1, got {k}")
        if not 0.0 <= disable_threshold <= 1.0:
            raise ValueError(
                f"disable_threshold must be in [0, 1], got {disable_threshold}"
            )
        if arm not in ("draft", "hidden"):
            raise ValueError(f"unknown spec arm {arm!r}")
        tok_vocab = engine.tokenizer.vocab_size
        self.engine = engine
        self.arm = arm
        self.k = int(k)
        self.disable_threshold = float(disable_threshold)
        self.ewma_alpha = float(ewma_alpha)
        self.min_rounds = int(min_rounds)
        self.stats = SpecStats()
        self._streams: dict[int, _Stream] = {}  # slot -> open stream
        if arm == "draft":
            if draft_params is None or draft_cfg is None:
                raise ValueError("arm='draft' needs draft_params + draft_cfg")
            if draft_cfg.vocab_size < tok_vocab:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} < tokenizer vocab "
                    f"{tok_vocab} — the draft cannot propose every legal token"
                )
            # Draft masks the same undecodable tail as the target (a draft
            # with a wider padded vocab must never propose past the
            # tokenizer).
            draft_limit = (
                tok_vocab if tok_vocab < draft_cfg.vocab_size else None
            )
            self.draft: DraftRunner | None = DraftRunner(
                draft_params, draft_cfg, vocab_limit=draft_limit
            )
            self.hidden_head: Params | None = None
            self._hidden_verify = None
        else:
            self.draft = None
            self.hidden_head = (
                hidden_head
                if hidden_head is not None
                else init_hidden_transfer(
                    jax.random.PRNGKey(hidden_seed), engine.cfg, self.k
                )
            )
            from k8s_llm_scheduler_tpu.spec.hidden import _hidden_verify_impl

            self._hidden_verify = jax.jit(
                functools.partial(
                    _hidden_verify_impl,
                    vocab_limit=engine._vocab_limit,
                    prefix_impl=engine.prefix_attn_impl,
                ),
                static_argnums=(1, 23, 24, 25),
                donate_argnums=(8, 9),
            )
        self._verify = jax.jit(
            functools.partial(
                _verify_impl,
                vocab_limit=engine._vocab_limit,
                prefix_impl=engine.prefix_attn_impl,
            ),
            static_argnums=(1, 22, 23),
            donate_argnums=(7, 8),
        )

    # ------------------------------------------------------------- plumbing
    def supports(self, prompt_ids: list[int], max_new_tokens: int) -> bool:
        """Whether this request can take the speculative path (the caller
        falls back to plain decode when not — never an error)."""
        eng = self.engine
        total = eng.prefix_len + len(prompt_ids)
        # The draft prefills the full context single-shot; cap it at the
        # engine's largest bucket like every other prefill. (The hidden
        # arm keeps the same bound: its block geometry rides the same
        # paged admission limits.)
        return total <= eng.prefill_buckets[-1]

    def _grammar_mode(self) -> tuple[str, jax.Array]:
        """(grammar impl for this dispatch, dense table or dummy).

        Greedy constrained verification uses the DENSE transition table
        when the engine's grammar exports one (the fused runtime's table
        — engine.dense_grammar()); the rejection sampler's proposal
        distributions live in K-space, so sampling mode keeps the sparse
        tables, as does a grammar past the dense-table byte cap."""
        eng = self.engine
        if not eng._constrained:
            return "none", eng._fused_dummy
        if eng.temperature == 0.0:
            dense = eng.dense_grammar()
            if dense is not None:
                return "dense", dense
        return "sparse", eng._fused_dummy

    def _round_io(self, slot: int, n_own: int, w: int, hard_cap: int):
        """Host-side page bookkeeping for one round: grow the slot to cover
        the block, then map each block position to (page, offset). Positions
        past `hard_cap` (draft tokens that could never be kept within the
        budget) route to the reserved scratch page 0."""
        eng = self.engine
        ps = eng.kv.page_size
        eng.kv.ensure_capacity(slot, min(n_own + w, hard_cap))
        pages = eng.kv.slot_pages(slot)
        page_ids = np.zeros(w, dtype=np.int32)
        offs = np.zeros(w, dtype=np.int32)
        for i, p in enumerate(range(n_own, n_own + w)):
            blk = p // ps
            if p < hard_cap and blk < len(pages):
                page_ids[i] = pages[blk]
                offs[i] = p % ps
        return jnp.asarray(page_ids), jnp.asarray(offs)

    def _propose_from(self, tok, pos: int, state, rng) -> _Proposal:
        """One fused draft proposal anchored at (tok @ pos, state) — host
        ints for a fresh round, device scalars for an AHEAD round."""
        eng = self.engine
        toks, states, idxs, logits = self.draft.propose(
            tok, pos, state,
            eng._sp_tokens, eng._sp_next, eng.tokenizer.pad_id,
            rng, eng.temperature, self.k, eng._constrained,
        )
        return _Proposal(
            anchor_tok=jnp.asarray(tok, dtype=jnp.int32),
            anchor_st=jnp.asarray(state, dtype=jnp.int32),
            pos=pos, toks=toks, states=states, idxs=idxs, logits=logits,
        )

    # --------------------------------------------------------------- stream
    def start(self, prompt_ids: list[int], max_new_tokens: int) -> _Stream:
        """Admit a request and open its speculative stream.

        Admission reuses the engine's own batched program (prompt KV lands
        in the slot's pages, the first token samples exactly as plain
        decode), then the slot is marked EXTERNAL and deactivated in the
        engine's decode batch: fused chunks for other slots keep
        dispatching while this stream drives its own rounds — the
        coexistence that replaced `engine.fused_hold`."""
        if self._streams:
            raise RuntimeError("one speculative stream at a time")
        eng = self.engine
        req_id = eng.add_request(prompt_ids, max_new_tokens)
        slot = next(s for s, r in eng._by_slot.items() if r.req_id == req_id)
        try:
            first_np, act_np, st_np = jax.device_get(
                (eng._first_d, eng._act_d, eng._st_d)
            )
            eng.stats["syncs"] += 1
            # Take the slot OUT of the engine's decode batch (after the
            # state fetch — deactivation clobbers the admission-time
            # active flag).
            req = eng._by_slot[slot]
            req.external = True
            eng._act_d = eng._act_d.at[slot].set(False)
            eng._budget_d = eng._budget_d.at[slot].set(0)
            eng._act_np[slot] = False
            eng._budget_np[slot] = 0

            n_prompt = len(prompt_ids)
            s = _Stream(
                req_id=req_id,
                slot=slot,
                n_prompt=n_prompt,
                max_new=max_new_tokens,
                hard_cap=n_prompt + max_new_tokens + 1,
                generated=[int(first_np[slot])],
                t_cur=int(first_np[slot]),
                st_cur=int(st_np[slot]),
                n_own=n_prompt,
                finished=not bool(act_np[slot]),
            )
            # Release the admission-time full decode reservation: the
            # spec loop grows per round and truncate() rolls rejected
            # tails back, which only means anything if the tail pages
            # are actually freeable.
            eng.kv.truncate(slot, s.n_own)
            if (
                self.arm == "draft"
                and not s.finished
                and max_new_tokens > 1
            ):
                prefix = eng._prefix or eng._get_empty_prefix()
                ctx = list(prefix.token_ids) + list(prompt_ids)
                t_d = time.perf_counter()
                with recorder.phase("spec_draft_prefill"):
                    # +2K+4 slack: the AHEAD proposal writes up to K+1
                    # past the block it anticipates.
                    self.draft.begin(
                        ctx, eng.tokenizer.pad_id,
                        extra=max_new_tokens + 2 * self.k + 4,
                    )
                s.seg["draft"] += time.perf_counter() - t_d
        except Exception:
            # A failed start must not leak the slot as an orphaned
            # external request (every harvest path skips external — no
            # later recovery path would ever free it).
            eng.release_slot(slot)
            raise
        self._streams[slot] = s
        return s

    def advance(self, s: _Stream):
        """Run ONE speculative round (or the terminal transition).

        Returns the Finished record once the request completes through
        the speculative path, else None. Callers may interleave
        engine.step_fused() between advances — spec rounds and fused
        chunks share one dispatch pipeline. On the auto-disable edge the
        slot HANDS BACK to the engine (`s.handed_off` flips True): the
        request finishes like any other through the caller's own
        step_fused()/decode_fused() harvest — advance() never consumes
        (and could otherwise silently drop) coexisting slots' Finished
        records. A failed round tears the stream down (slot + pages
        released, the one-stream guard cleared) before re-raising."""
        if s.handed_off:
            raise RuntimeError(
                "stream handed off to the engine (auto-disable); harvest "
                "its Finished via step_fused/decode_fused"
            )
        if self._streams.get(s.slot) is not s:
            # finished / torn down: the slot may already serve another
            # request — touching it again could release an innocent
            # coexisting stream's state
            raise RuntimeError("speculative stream is closed")
        try:
            if s.finished or len(s.generated) >= s.max_new:
                return self._finish(s)
            if not s.disabled:
                if self.arm == "hidden":
                    self._round_hidden(s)
                else:
                    self._round_draft(s)
            if s.finished or len(s.generated) >= s.max_new:
                return self._finish(s)
            if s.disabled:
                self._hand_off(s)
            return None
        except Exception:
            self._streams.pop(s.slot, None)
            if (
                s.slot in self.engine._by_slot
                and self.engine._by_slot[s.slot].req_id == s.req_id
            ):
                self.engine.release_slot(s.slot)
            raise

    # -------------------------------------------------------- draft rounds
    def _round_draft(self, s: _Stream) -> None:
        eng = self.engine
        K = self.k
        w = K + 1
        pad = eng.tokenizer.pad_id
        prefix = eng._prefix or eng._get_empty_prefix()
        abs_pos = eng.prefix_len + s.n_own
        grammar, dense_tbl = self._grammar_mode()

        prop = s.pending
        s.pending = None
        overlapped = prop is not None and prop.pos == abs_pos
        if not overlapped:
            if prop is not None:
                self.stats.ahead_wasted += 1
            t_d = time.perf_counter()
            eng._rng, r_draft = jax.random.split(eng._rng)
            with recorder.phase("spec_draft"):
                prop = self._propose_from(
                    s.t_cur, abs_pos, s.st_cur, r_draft
                )
            s.seg["draft"] += time.perf_counter() - t_d

        blk_tok = jnp.concatenate([prop.anchor_tok[None], prop.toks[:K]])
        mask_states = jnp.concatenate(
            [prop.anchor_st[None], prop.states[:K]]
        )[:w]
        positions = jnp.arange(abs_pos, abs_pos + w, dtype=jnp.int32)
        page_ids, offs = self._round_io(s.slot, s.n_own, w, s.hard_cap)
        table_row = eng.kv.page_tables()[s.slot][None, :]

        t_v = time.perf_counter()
        eng._rng, r_verify = jax.random.split(eng._rng)
        with recorder.phase("spec_verify"):
            a_d, t_next_d, st_next_d, eng.kv.k, eng.kv.v = self._verify(
                eng.params, eng.cfg,
                blk_tok, positions,
                prefix.k, prefix.v, jnp.int32(prefix.length),
                eng.kv.k, eng.kv.v,
                table_row, jnp.int32(s.n_own), page_ids, offs,
                mask_states, prop.idxs, prop.logits,
                eng._sp_tokens, eng._sp_next, dense_tbl,
                jnp.int32(pad),
                r_verify, jnp.float32(eng.temperature),
                grammar, eng.temperature == 0.0,
            )
        s.seg["verify"] += time.perf_counter() - t_v

        # AHEAD proposal for round n+1, enqueued BEFORE the round's fetch:
        # the draft continues its own chain through the bonus-token guess
        # while the target verify (already dispatched) runs — this is the
        # overlap. Skipped when the budget could never use it or the
        # draft buffer would overflow.
        ahead = None
        ahead_pos = abs_pos + K + 1
        remaining = s.max_new - len(s.generated)
        if remaining > K + 1 and ahead_pos + K + 1 <= self.draft.capacity:
            t_d = time.perf_counter()
            eng._rng, r_ahead = jax.random.split(eng._rng)
            with recorder.phase("spec_draft"):
                ahead = self._propose_from(
                    prop.toks[K], ahead_pos, prop.states[K], r_ahead
                )
            s.seg["draft"] += time.perf_counter() - t_d

        # THE round's one host fetch: accept verdict + the block's token
        # values (the ahead proposal's outputs stay device-resident).
        t_v = time.perf_counter()
        a_np, t_next_np, st_next_np, toks_np, states_np = jax.device_get(
            (a_d, t_next_d, st_next_d, prop.toks, prop.states)
        )
        eng.stats["syncs"] += 1
        s.seg["verify"] += time.perf_counter() - t_v

        a = int(a_np)
        t_next, st_next = int(t_next_np), int(st_next_np)
        if overlapped:
            self.stats.overlapped_rounds += 1
            s.overlapped += 1
        self._resolve_round(
            s, a, t_next, st_next,
            [(int(toks_np[i]), int(states_np[i])) for i in range(a)],
        )
        # Adopt the ahead block when the chain it anticipated is exactly
        # the chain that happened: full accept AND the bonus token (and
        # its DFA state) match the draft's guess.
        if (
            ahead is not None
            and not s.finished
            and not s.disabled
            and len(s.generated) < s.max_new
            and a == K
            and t_next == int(toks_np[K])
            and st_next == int(states_np[K])
            and eng.prefix_len + s.n_own == ahead.pos
        ):
            s.pending = ahead
        elif ahead is not None:
            self.stats.ahead_wasted += 1

    # ------------------------------------------------------- hidden rounds
    def _round_hidden(self, s: _Stream) -> None:
        eng = self.engine
        K = self.k
        pad = eng.tokenizer.pad_id
        prefix = eng._prefix or eng._get_empty_prefix()
        abs_pos = eng.prefix_len + s.n_own
        grammar, dense_tbl = self._grammar_mode()

        pend = s.pending
        s.pending = None
        if pend is not None and pend.pos == abs_pos:
            w = K + 1
            blk_tok = jnp.concatenate(
                [jnp.asarray([s.t_cur], dtype=jnp.int32), pend.toks]
            )
            mask_states = jnp.concatenate(
                [jnp.asarray([s.st_cur], dtype=jnp.int32), pend.states]
            )[:w]
            choice_idx, q_logits = pend.idxs, pend.logits
            drafts = [
                (int(pend.toks_np[i]), int(pend.states_np[i]))
                for i in range(K)
            ]
            overlapped = True
        else:
            # Bootstrap geometry (W=1): no proposals to verify yet — the
            # program processes the current token, samples its successor,
            # and produces the first transfer-head proposal block.
            w = 1
            blk_tok = jnp.asarray([s.t_cur], dtype=jnp.int32)
            mask_states = jnp.asarray([s.st_cur], dtype=jnp.int32)
            choice_idx = jnp.zeros((0,), dtype=jnp.int32)
            q_logits = jnp.zeros((0, 1), dtype=jnp.float32)
            drafts = []
            overlapped = False
        positions = jnp.arange(abs_pos, abs_pos + w, dtype=jnp.int32)
        page_ids, offs = self._round_io(s.slot, s.n_own, w, s.hard_cap)
        table_row = eng.kv.page_tables()[s.slot][None, :]

        t_v = time.perf_counter()
        eng._rng, r_verify = jax.random.split(eng._rng)
        with recorder.phase("spec_verify"):
            (
                a_d, t_next_d, st_next_d,
                g_toks_d, g_states_d, g_idx_d, g_logits_d,
                eng.kv.k, eng.kv.v,
            ) = self._hidden_verify(
                eng.params, eng.cfg, self.hidden_head,
                blk_tok, positions,
                prefix.k, prefix.v, jnp.int32(prefix.length),
                eng.kv.k, eng.kv.v,
                table_row, jnp.int32(s.n_own), page_ids, offs,
                mask_states, choice_idx, q_logits,
                eng._sp_tokens, eng._sp_next, dense_tbl,
                jnp.int32(pad),
                r_verify, jnp.float32(eng.temperature),
                grammar, eng.temperature == 0.0, K,
            )
        # The round's one fetch: verdict + the NEXT block's guess values
        # (the guesses' device arrays stay resident for round n+1's
        # dispatch — host copies serve the emit path without a 2nd sync).
        a_np, t_next_np, st_next_np, g_toks_np, g_states_np = jax.device_get(
            (a_d, t_next_d, st_next_d, g_toks_d, g_states_d)
        )
        eng.stats["syncs"] += 1
        s.seg["verify"] += time.perf_counter() - t_v

        a = int(a_np)
        t_next, st_next = int(t_next_np), int(st_next_np)
        if overlapped:
            # Proposals were computed inside the PREVIOUS round's program
            # — the propose stream is fully hidden behind the verify.
            self.stats.overlapped_rounds += 1
            s.overlapped += 1
            self._resolve_round(s, a, t_next, st_next, drafts[:a])
        else:
            # Bootstrap: one target-sampled token, no proposals verified.
            self._resolve_round(
                s, a, t_next, st_next, [], count_round=False
            )
        if (
            not s.finished
            and not s.disabled
            and s.max_new - len(s.generated) > 1
        ):
            s.pending = _HiddenBlock(
                pos=self.engine.prefix_len + s.n_own,
                toks=g_toks_d, states=g_states_d,
                idxs=g_idx_d, logits=g_logits_d,
                toks_np=np.asarray(g_toks_np),
                states_np=np.asarray(g_states_np),
            )

    # ------------------------------------------------------------- resolve
    def _resolve_round(
        self,
        s: _Stream,
        a: int,
        t_next: int,
        st_next: int,
        accepted: list[tuple[int, int]],
        count_round: bool = True,
    ) -> None:
        """Emit the round's target-consistent tokens, roll back the
        rejected tail's pages, and update the acceptance EWMA."""
        eng = self.engine
        eos = eng.tokenizer.eos_id
        done_state = int(eng._done_state)
        if count_round:
            s.rounds += 1
            self.stats.rounds += 1
            self.stats.proposed += self.k
            self.stats.accepted += a

        t_r = time.perf_counter()
        cand = list(accepted)
        cand.append((t_next, st_next))
        for tok, stt in cand:
            if len(s.generated) >= s.max_new:
                break
            s.generated.append(tok)
            self.stats.emitted += 1
            if tok == eos or stt == done_state:
                s.finished = True
                break
            s.t_cur, s.st_cur = tok, stt
        # n_own counts tokens whose KV is resident: t_cur's KV lands only
        # when it is processed next round, so the resident count is
        # prompt + (emitted - 1).
        s.n_own = s.n_prompt + len(s.generated) - 1
        # Paged-KV rollback: free the rejected tail's pages.
        eng.kv.truncate(s.slot, s.n_own)
        s.seg["rollback"] += time.perf_counter() - t_r

        if count_round:
            rate = a / self.k
            s.ewma = (
                rate
                if s.ewma is None
                else self.ewma_alpha * rate + (1 - self.ewma_alpha) * s.ewma
            )
            # PER-REQUEST warmup (s.rounds, not the decoder-global round
            # counter): every request gets min_rounds of EWMA settling
            # before it can disable — a global counter would let any
            # request after the first disable on its very first bad round.
            if (
                s.rounds >= self.min_rounds
                and not s.finished
                and s.ewma < self.disable_threshold
            ):
                s.disabled = True
                self.stats.disables += 1

    # ------------------------------------------------------------- generate
    def generate(self, prompt_ids: list[int], max_new_tokens: int = 200):
        """Speculative replacement for the engine's plain generate():
        greedy output is token-identical to plain decode, sampling output
        follows the target distribution exactly (spec/verify.py)."""
        eng = self.engine
        if not self.supports(prompt_ids, max_new_tokens):
            self.stats.unsupported_requests += 1
            return eng.generate(prompt_ids, max_new_tokens, use_spec=False)
        self.stats.requests += 1
        from k8s_llm_scheduler_tpu.observability import spans

        s0 = self.stats
        before = (s0.proposed, s0.accepted, s0.rounds, s0.disables)
        s = self.start(prompt_ids, max_new_tokens)
        try:
            with spans.span("spec_decode", layer="engine") as sp:
                fin = None
                while fin is None and not s.handed_off:
                    fin = self.advance(s)
                if fin is None:
                    # Auto-disable handed the slot to the engine: finish
                    # it through the fused runtime. Single-request
                    # surface — same Finished-filtering semantics as
                    # engine.generate().
                    with recorder.phase("spec_fallback"):
                        while fin is None:
                            for f in eng.step_fused():
                                if f.req_id == s.req_id:
                                    fin = f
                if sp is not None:
                    sp.attrs.update(
                        arm=self.arm,
                        proposed=s0.proposed - before[0],
                        accepted=s0.accepted - before[1],
                        rejected=(s0.proposed - before[0])
                        - (s0.accepted - before[1]),
                        rounds=s0.rounds - before[2],
                        disabled=bool(s0.disables - before[3]),
                    )
            return fin
        except Exception:
            # Mirror add_requests' rollback: a failed round must not leak
            # the slot or its pages (no later recovery path would — the
            # request never reaches step()'s teardown).
            self._streams.pop(s.slot, None)
            if s.slot in eng._by_slot:
                eng.release_slot(s.slot)
            raise

    # ------------------------------------------------------------- teardown
    def _profile_stream(self, s: _Stream, disabled: bool) -> None:
        prof = self.engine.profiler
        if prof is None:
            return
        prof.on_spec(
            wall_s=time.perf_counter() - s.t0,
            draft_s=s.seg["draft"],
            verify_s=s.seg["verify"],
            rollback_s=s.seg["rollback"],
            rounds=s.rounds,
            overlapped_rounds=s.overlapped,
            tokens=max(len(s.generated) - 1, 0),
            arm=self.arm,
            disabled=disabled,
        )

    def _finish(self, s: _Stream):
        """Complete the request: free the slot and build Finished exactly
        like the plain step() path does."""
        from k8s_llm_scheduler_tpu.engine.engine import Finished

        eng = self.engine
        req = eng._by_slot[s.slot]
        self._streams.pop(s.slot, None)
        eng.release_slot(s.slot)
        ids = s.generated[: s.max_new]
        # First token is accounted like the plain path (not a decode token).
        eng.stats["decode_tokens"] += max(len(ids) - 1, 0)
        eng.stats["completed"] += 1
        self._profile_stream(s, disabled=False)
        return Finished(
            req_id=s.req_id,
            token_ids=ids,
            text=eng.tokenizer.decode(ids),
            latency_ms=(time.perf_counter() - req.submitted_at) * 1000.0,
        )

    def _hand_off(self, s: _Stream) -> None:
        """Auto-disable hand-off: restore the slot's device-resident
        decode state and hand it BACK to the engine's decode batch
        (external flag cleared — the disable edge re-arms the FUSED
        path, it never strands the slot on the slow chunked loop). The
        request finishes like any other engine request: the caller's own
        step_fused()/decode_fused() harvest returns its Finished record
        — driving the engine from HERE would consume (and drop)
        coexisting slots' completions out from under the caller."""
        eng = self.engine
        self.stats.fallback_requests += 1
        self._streams.pop(s.slot, None)
        self._profile_stream(s, disabled=True)
        remaining = s.max_new - len(s.generated)
        req = eng._by_slot[s.slot]
        req.generated = list(s.generated)
        req.first_pending = False
        req.external = False
        eng.kv.ensure_capacity(s.slot, s.n_own + remaining + 1)
        eng._tok_d = eng._tok_d.at[s.slot].set(s.t_cur)
        eng._pos_d = eng._pos_d.at[s.slot].set(eng.prefix_len + s.n_own)
        eng._act_d = eng._act_d.at[s.slot].set(True)
        eng._st_d = eng._st_d.at[s.slot].set(s.st_cur)
        eng._budget_d = eng._budget_d.at[s.slot].set(remaining)
        eng._act_np[s.slot] = True
        eng._budget_np[s.slot] = remaining
        # The spec-emitted tokens are already in req.generated; the plain
        # path's completion accounting takes over from here.
        eng.stats["decode_tokens"] += max(len(s.generated) - 1, 0)
        s.handed_off = True

    # ----------------------------------------------------------------- swap
    def on_swap(self) -> None:
        """Engine hot-swap hook (engine.swap_params calls this BEFORE
        installing new weights): roll back every open stream's
        speculative tail via the grammar-safe PagedKVCache.truncate and
        drop device-resident proposal blocks — they were computed under
        the superseded weights and must never seed a post-swap round.
        Already-emitted tokens stand (identical-params swaps are the only
        mid-stream-legal kind, exactly the paged in-flight contract
        engine.swap_params documents); the stream re-proposes fresh from
        its last verified token on the next advance."""
        for s in self._streams.values():
            self.engine.kv.truncate(s.slot, s.n_own)
            if s.pending is not None:
                s.pending = None
                self.stats.ahead_wasted += 1
            self.stats.swap_rollbacks += 1

    @property
    def open_streams(self) -> int:
        return len(self._streams)
