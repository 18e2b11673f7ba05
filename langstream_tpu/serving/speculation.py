"""Prompt-lookup (n-gram) draft index for self-speculative decoding.

Decode emits ONE token per weight read; speculation amortizes that read
over k+1 tokens by proposing drafts cheaply on the HOST and verifying them
in one multi-token device dispatch (engine._paged_verify_chunk). This module is
the proposer: no draft model, no extra weights — a per-slot n-gram index
over prompt + generated tokens (the "prompt lookup" scheme: chat, RAG and
code traffic constantly re-emits spans of its own context, and greedy
decode on a fixed cache frequently enters literal cycles) maps the current
tail n-gram to the continuation that followed its previous occurrence.

Wrong drafts cost only the wasted verify columns — acceptance is decided
against the model's own outputs on device (serving/sampling.py
speculative_verify), so a bad proposal can never change what is emitted.
That is what keeps the index allowed to be this simple.
"""

from __future__ import annotations


class NGramIndex:
    """Draft index for ONE slot's token stream.

    For each gram size n in [min_n, max_n] the index maps the n-gram to the
    continuation positions of its two most recent occurrences. ``propose``
    looks up the current tail gram (largest n first — longer matches are
    more specific, so their historical continuation is likelier to repeat)
    and returns the tokens that followed the previous occurrence. The
    latest occurrence of the tail gram is always the tail itself, which has
    no continuation yet — hence the two-deep history.
    """

    __slots__ = ("max_n", "min_n", "tokens", "_maps")

    def __init__(self, max_n: int = 3, min_n: int = 1) -> None:
        if min_n < 1 or max_n < min_n:
            raise ValueError(f"bad n-gram range [{min_n}, {max_n}]")
        self.max_n = max_n
        self.min_n = min_n
        self.tokens: list[int] = []
        # gram -> (continuation pos of latest occurrence, of the one before)
        self._maps: dict[int, dict[tuple, tuple]] = {
            n: {} for n in range(min_n, max_n + 1)
        }

    def __len__(self) -> int:
        return len(self.tokens)

    def append(self, token: int) -> None:
        self.tokens.append(int(token))
        i = len(self.tokens)
        for n, m in self._maps.items():
            if i >= n:
                gram = tuple(self.tokens[i - n : i])
                prev = m.get(gram)
                m[gram] = (i, prev[0] if prev is not None else None)

    def extend(self, tokens) -> None:
        for t in tokens:
            self.append(t)

    def propose(self, k: int) -> list[int]:
        """``k`` draft tokens continuing the current tail, or [] when no
        tail gram has a prior occurrence. A continuation that runs into the
        tail extends PERIODICALLY (period = distance between the two
        occurrences): cyclic output — the single most common repetitive
        pattern greedy decode produces — would otherwise cap every proposal
        at one period and waste most of the verify chunk's k columns. A
        wrong extension only costs rejected columns; the verifier decides."""
        length = len(self.tokens)
        for n in range(self.max_n, self.min_n - 1, -1):
            if length < n:
                continue
            hit = self._maps[n].get(tuple(self.tokens[length - n :]))
            if hit is None:
                continue
            latest, prev = hit
            # the latest occurrence of the tail gram IS the tail (its
            # continuation position == length): use the one before
            pos = prev if latest >= length else latest
            if pos is None or pos >= length:
                continue
            period = length - pos
            return [
                self.tokens[pos + (i % period)] for i in range(k)
            ]
        return []
