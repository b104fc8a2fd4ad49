"""End-to-end metrics from the load generator's records, over [w0, w1).

* ``itl_p95_ms``: over every gap between consecutive streamed tokens that
  ends in the window, plus each gap still open at the close, counted up to
  the close.
* ``out_tok_s``: tokens streamed in the window over the window's length.

Percentiles are numpy's default (linear) over all samples: no medians of
chunks.
"""
from __future__ import annotations

import numpy as np


def itl_s(recs, w0, w1) -> list:
    out = []
    for r in recs:
        t = [x for x in r.times if x < w1]
        out.extend(b - a for a, b in zip(t, t[1:]) if b >= w0)
        if t and (r.done is None or r.done >= w1):  # still streaming
            out.append(w1 - t[-1])
    return out


def tokens_in(recs, w0, w1) -> int:
    return sum(1 for r in recs for x in r.times if w0 <= x < w1)


def p95_ms(values) -> float | None:
    if not values:
        return None
    return float(np.percentile(np.asarray(values), 95) * 1e3)
