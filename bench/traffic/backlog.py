"""Closed backlog: ``outstanding`` requests are due at time 0, and each
one that finishes is replaced at once by the next, so the engine's queue
never empties.  The i-th request's prompt and output lengths come from the
mix's ``prompt`` and ``output``, drawn from ``base_seed`` in blocks of
``BLOCK`` requests (the same for every run seed); its token ids from the
run seed.  A run answers for the requests whose first token streamed
before the close and that were still streaming at the open; one still
queued at the close is not a failure."""
from __future__ import annotations

from typing import Optional

import numpy as np

import traffic as T

BLOCK = 256  # requests whose lengths are drawn together


class Source:
    def __init__(self, mix, seed, seconds, vocab, slots):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.count = 0  # requests made so far
        self._blocks: dict = {}
        self.due = [self._next(0.0) for _ in range(mix["outstanding"])]

    def lengths(self, i: int) -> tuple:
        """(prompt length, output length) of request ``i``."""
        k = i // BLOCK
        if k not in self._blocks:
            rng = np.random.default_rng([self.mix["base_seed"], k])
            self._blocks[k] = (T.lengths(rng, self.mix["prompt"], BLOCK),
                               T.lengths(rng, self.mix["output"], BLOCK))
        plen, olen = self._blocks[k]
        return int(plen[i % BLOCK]), int(olen[i % BLOCK])

    def _next(self, due: float):
        i = self.count
        self.count += 1
        plen, olen = self.lengths(i)
        return T.Arrival(i, due, T.tokens(self.seed, (0, i), plen,
                                          self.vocab), olen)

    def poll(self, now: float, queued: int) -> list:
        out = [a for a in self.due if a.due <= now]
        self.due = [a for a in self.due if a.due > now]
        return out

    def next_due(self) -> Optional[float]:
        return min((a.due for a in self.due), default=None)

    def on_done(self, arrival, output, now) -> None:
        self.due.append(self._next(now))

    def measured(self, recs, w0: float, w1: float) -> list:
        return [r for r in recs if r.times and r.times[0] < w1
                and (r.done is None or r.done >= w0)]

    def max_position(self, max_len: int) -> int:
        return min(self.mix["prompt"]["max"] + self.mix["output"]["max"],
                   max_len - 1)
