"""Speculative decoding: draft-token proposers + configuration.

Speculative decoding amortizes one expensive target-model pass over K
tokens per step (the standard TPU LLM serving lever — see *Ragged Paged
Attention* and the Gemma serving notes in PAPERS.md): a cheap PROPOSER
guesses K draft tokens, the target engine scores all of them in ONE
fixed-shape `verify_step`, and the scheduler keeps the longest prefix of
drafts that match what the target itself would have sampled, plus one
bonus/correction token. Greedy speculative decode is therefore
token-for-token identical to plain decode — only faster.

Two proposers ship:

- `NGramProposer` — model-free prompt-lookup (the n-gram trick): match the
  context's suffix n-gram against its own history and propose the tokens
  that followed last time. Zero weights, zero device work, CPU-testable;
  shines on repetition-heavy traffic (code, retrieval-augmented prompts,
  chat templates).
- `DraftEngineProposer` — a small draft `EngineCore` (same vocab) decodes
  K tokens greedily per step against its own paged cache, synced to the
  verified context via catch-up decode + `trim` rollback.

Both implement the `Proposer` protocol the scheduler programs against.
Proposals are best-effort: fewer than K (or zero) draft tokens is a valid
answer and the scheduler pads the fixed-K verify batch around it.
"""
from __future__ import annotations

from typing import Dict, List, Protocol, runtime_checkable

import numpy as np

from ..inference.cache import KVCacheExhausted, SequenceTooLong

__all__ = ["Proposer", "NGramProposer", "DraftEngineProposer",
           "SpecDecodeConfig"]


@runtime_checkable
class Proposer(Protocol):
    """Draft-token source for speculative decoding."""

    def propose(self, seq_id: int, context: np.ndarray,
                k: int) -> List[int]:
        """Return up to `k` draft tokens continuing `context` (the full
        committed token stream INCLUDING the pending last token). May
        return fewer — or none — when it has no confident guess."""
        ...

    def release(self, seq_id: int) -> None:
        """Drop any per-sequence state (request finished or preempted)."""
        ...


class SpecDecodeConfig:
    """Speculative-decoding knobs for the scheduler.

    `num_draft_tokens` (K) is FIXED for the lifetime of the scheduler: the
    verify pass always scores K+1 tokens per lane, so the decode steady
    state stays a single compiled program (zero recompiles)."""

    def __init__(self, proposer: Proposer, num_draft_tokens: int = 4):
        if num_draft_tokens < 1:
            raise ValueError(
                f"num_draft_tokens must be >= 1, got {num_draft_tokens}")
        self.proposer = proposer
        self.num_draft_tokens = int(num_draft_tokens)


class NGramProposer:
    """Prompt-lookup proposer: longest-suffix n-gram self-match.

    For n-gram sizes `max_ngram` down to `min_ngram`, find the RIGHTMOST
    earlier occurrence of the context's trailing n-gram and propose the
    tokens that followed it. Pure host bookkeeping — no model, no device
    work, no per-sequence state."""

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(f"need 1 <= min_ngram <= max_ngram, got "
                             f"({min_ngram}, {max_ngram})")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def propose(self, seq_id: int, context: np.ndarray,
                k: int) -> List[int]:
        ext = [int(t) for t in np.asarray(context).reshape(-1)]
        props: List[int] = []
        # self-extending lookup: after taking the continuation of a match,
        # append it to the (virtual) context and re-match — a context that
        # ends in a cycle (the repetition-heavy case this proposer is FOR)
        # keeps yielding drafts instead of truncating at the rightmost
        # match, which for a constant tail sits one token from the end.
        while len(props) < k:
            taken = self._match_one(ext, k - len(props))
            if not taken:
                break
            props.extend(taken)
            ext.extend(taken)
        return props

    def _match_one(self, ext: List[int], k: int) -> List[int]:
        """Tokens following the rightmost history match of the longest
        suffix n-gram (byte-level rfind: this runs per lane per decode
        step, so the scan is one C-speed pass plus an alignment walk for
        the rare misaligned byte hit, not numpy window allocations)."""
        n = len(ext)
        if n < 2:
            return []
        blob = np.asarray(ext, np.int32).tobytes()
        for m in range(min(self.max_ngram, n - 1), self.min_ngram - 1, -1):
            pat = blob[4 * (n - m):]
            # window start j needs j + m <= n - 1 (match inside history,
            # strictly before the suffix itself): byte end limit 4*(n-1)
            idx = blob.rfind(pat, 0, 4 * (n - 1))
            while idx >= 0 and idx % 4:
                idx = blob.rfind(pat, 0, idx + len(pat) - 1)
            if idx >= 0:
                start = idx // 4 + m
                return ext[start:start + k]
        return []

    def release(self, seq_id: int) -> None:
        pass


class DraftEngineProposer:
    """Draft-model proposer over a second (small) `EngineCore`.

    The draft engine keeps its own paged cache in sync with each verified
    context through its `ragged_step`, one lane a call: the first context
    goes in as one step (`q_len` = its length, the packed buffer padded to
    a length bucket so the compile count stays bounded; guard slots write
    nothing), catch-up tokens and the K greedy proposals as `q_len = 1`
    steps, and the cache is `trim`med back to the verified length —
    rejected speculation never pollutes the draft state. All failures
    (draft pool exhausted, sequence over the draft's length cap) degrade
    to "no proposal", never to an error on the serving path."""

    def __init__(self, engine):
        self.engine = engine
        self._synced: Dict[int, int] = {}   # seq_id -> tokens in draft cache

    # -- helpers ----------------------------------------------------------
    def _step(self, seq_id: int, tokens: np.ndarray, n: int) -> np.ndarray:
        """The last live row's logits [V] of one ragged step that appends
        `tokens[:n]` to `seq_id`, whose cache then holds `seq_len` tokens."""
        mgr = self.engine.manager
        logits = self.engine.ragged_step(
            tokens, np.asarray([n], np.int32),
            np.asarray([mgr.seq_len(seq_id)], np.int32),
            mgr.block_table_array([seq_id]))
        return np.asarray(logits[n - 1])

    def _decode_one(self, token: int, seq_id: int) -> np.ndarray:
        return self._step(seq_id, np.asarray([token], np.int32), 1)

    def _prefill(self, seq_id: int, ctx: np.ndarray) -> np.ndarray:
        mgr = self.engine.manager
        n = len(ctx)
        mgr.allocate(seq_id, n)     # typed SequenceTooLong over the cap
        self._synced[seq_id] = n    # from here on release() frees the lease
        cap = mgr.max_blocks_per_seq * mgr.block_size
        bucket = mgr.block_size
        while bucket < n:
            bucket = min(bucket * 2, cap)
        padded = np.zeros((bucket,), np.int32)
        padded[:n] = ctx
        return self._step(seq_id, padded, n)

    # -- Proposer protocol -------------------------------------------------
    def propose(self, seq_id: int, context: np.ndarray,
                k: int) -> List[int]:
        mgr = self.engine.manager
        ctx = np.asarray(context, np.int32).reshape(-1)
        n = len(ctx)
        if n == 0:
            return []
        try:
            if seq_id not in self._synced:
                logits = self._prefill(seq_id, ctx)
            else:
                m = self._synced[seq_id]
                if m > n:          # stale state past the verified context
                    mgr.trim(seq_id, n)
                    m = n
                if m == n:         # re-score the last token (no growth)
                    logits = self._decode_one(int(ctx[-1]), seq_id)
                else:              # catch-up: write KV for ctx[m:n]
                    for j in range(m, n):
                        mgr.append_token(seq_id)
                        logits = self._decode_one(int(ctx[j]), seq_id)
                    self._synced[seq_id] = n
            # greedy draft rollout; proposal KV is trimmed away below
            props = [int(np.argmax(logits))]
            while len(props) < k:
                try:
                    mgr.append_token(seq_id)
                except (KVCacheExhausted, SequenceTooLong):
                    break
                logits = self._decode_one(props[-1], seq_id)
                props.append(int(np.argmax(logits)))
            mgr.trim(seq_id, n)
            return props
        except Exception:
            # draft pool pressure (KVCacheExhausted/SequenceTooLong) — or
            # ANY draft-engine fault: propose nothing and drop our lease
            # so the next call starts clean. Catching only the cache
            # types used to leak the lease + sync entry when the draft
            # engine itself raised (the scheduler swallows the exception
            # outside, where our lease is invisible).
            self.release(seq_id)
            return []

    def release(self, seq_id: int) -> None:
        if seq_id in self._synced:
            self._synced.pop(seq_id, None)
            try:
                self.engine.manager.free(seq_id)
            except KeyError:
                pass
