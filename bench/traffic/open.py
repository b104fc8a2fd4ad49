"""Open loop: Poisson arrivals at ``rate_per_s`` over ``warmup_s +
seconds``, the gaps scaled to fill that span exactly; prompt and output
lengths from the mix's ``prompt`` and ``output``.  A run answers for the
requests due in its window."""
from __future__ import annotations

from typing import Optional

import numpy as np

import traffic as T


class Source:
    def __init__(self, mix, seed, seconds, vocab, slots):
        span = mix["warmup_s"] + seconds
        base = np.random.default_rng(mix["base_seed"])
        times = T.arrival_times(base, mix["rate_per_s"], span)
        n = len(times)
        plen = T.lengths(base, mix["prompt"], n)
        olen = T.lengths(base, mix["output"], n)
        self.mix = mix
        self.pending = [T.Arrival(i, float(times[i]),
                                  T.tokens(seed, (0, i), int(plen[i]), vocab),
                                  int(olen[i])) for i in range(n)]
        self.pending.reverse()

    def poll(self, now: float, queued: int) -> list:
        out = []
        while self.pending and self.pending[-1].due <= now:
            out.append(self.pending.pop())
        return out

    def next_due(self) -> Optional[float]:
        return self.pending[-1].due if self.pending else None

    def on_done(self, arrival, output, now) -> None:
        pass

    def measured(self, recs, w0: float, w1: float) -> list:
        return [r for r in recs if w0 <= r.due < w1]

    def max_position(self, max_len: int) -> int:
        return min(self.mix["prompt"]["max"] + self.mix["output"]["max"],
                   max_len - 1)
