"""Speculative decoding for the continuous scheduler: n-gram prompt-lookup
drafting on the host, greedy acceptance on the device.

The technique vLLM ships as "prompt lookup decoding" / ngram speculation (no
reference counterpart: the reference delegates inference to external
providers, SURVEY §0):

- **Drafting is free**: instead of a draft model, the proposer looks the
  trailing n-gram of the sequence up in its own history (prompt + generated
  text repeats itself: quotes, code identifiers, RAG copies). Host-side, no
  device work at all.
- **Verification rides the mixed step**: a speculating slot's k drafts plus
  its last committed token are ONE ragged span of ``programs.spec_mixed_step``;
  on a bandwidth-bound decode, weights dominate HBM traffic, so verifying k+1
  positions costs nearly the same as decoding one token. Greedy acceptance:
  drafts match while ``draft[i] == argmax[i-1]``; the verify output at the
  last accepted position is a free "bonus" token, so every span commits
  between 1 and k+1 tokens.
- **A rejected suffix never commits**: its KV sits beyond the committed
  length, is masked out of attention, and is rewritten before any later read.

Greedy only (temperature 0): lossless, emitted tokens are bit-identical to
plain decode (pinned by tests/test_scheduler_spec.py).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


class NgramProposer:
    """Incremental n-gram index over one sequence's tokens.

    For each n in [min_n, max_n], remembers the position right after the most
    recent occurrence of every n-gram. ``propose`` matches the current tail
    n-gram (longest n first) and copies up to k tokens that followed its
    previous occurrence.
    """

    def __init__(self, max_n: int = 3, min_n: int = 1, k: int = 8) -> None:
        if not 1 <= min_n <= max_n:
            raise ValueError(f"bad n-gram range [{min_n}, {max_n}]")
        self.max_n = max_n
        self.min_n = min_n
        self.k = k
        self.tokens: list[int] = []
        #: ngram -> (end of latest occurrence, end of previous occurrence).
        #: The sequence tail is always its own latest occurrence, so propose()
        #: reads the PREVIOUS slot.
        self._index: dict[tuple[int, ...], tuple[int, Optional[int]]] = {}
        #: propose() memo keyed by the sequence length it was computed at —
        #: the scheduler probes the proposer several times per round (ring
        #: gate, round gate, plan), all against the same unchanged tail
        self._memo: tuple[int, Optional[list[int]]] = (-1, None)

    def extend(self, tokens: list[int]) -> None:
        for tok in tokens:
            self.tokens.append(tok)
            end = len(self.tokens)
            for n in range(self.min_n, self.max_n + 1):
                if end >= n:
                    gram = tuple(self.tokens[end - n:end])
                    prev = self._index.get(gram)
                    self._index[gram] = (end, prev[0] if prev else None)

    def propose(self) -> Optional[list[int]]:
        """Up to k draft tokens, or None when no tail n-gram has recurred.
        Memoized per sequence length (repeat probes between extends are
        free)."""
        end = len(self.tokens)
        if self._memo[0] == end:
            return self._memo[1]
        result: Optional[list[int]] = None
        for n in range(self.max_n, self.min_n - 1, -1):
            if end < n:
                continue
            hit = self._index.get(tuple(self.tokens[end - n:end]))
            if hit is None:
                continue
            latest, prev = hit
            pos = prev if latest == end else latest
            if pos is not None:
                drafts = self.tokens[pos:pos + self.k]
                if drafts:
                    result = drafts
                    break
        self._memo = (end, result)
        return result


def greedy_accept_counts(outs: jnp.ndarray, drafts: jnp.ndarray,
                         draft_lens: jnp.ndarray) -> jnp.ndarray:
    """Device-side greedy acceptance: ``outs`` [N, S] is the per-position
    argmax of a verify span (S = k+1), ``drafts`` [N, S-1] the proposed
    tokens, ``draft_lens`` [N] how many are real (the rest padding). Returns
    [N] — the number of leading drafts equal to the model's own argmax
    continuation: the scheduler's on-device accept
    (``programs.spec_mixed_step``)."""
    S = outs.shape[1]
    pos = jnp.arange(S - 1, dtype=jnp.int32)[None, :]
    match = (drafts == outs[:, :-1]) & (pos < draft_lens[:, None])
    return jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                   axis=1).astype(jnp.int32)
