"""What every traffic kind shares; a mix is a data file, a kind a file.

``traffic/<mix>.json`` gives a mix's parameters and names its ``kind``;
``traffic/<kind>.py`` is the generator that reads them, found by name
(``run.load_reader("traffic", kind)``).  Its ``Source(mix, seed, seconds,
vocab, slots)`` is the timed stream of requests the load generator polls:

* ``poll(now, queued)``: the arrivals due by ``now``;
* ``next_due()``: when the next one is due (None: none is scheduled);
* ``on_done(arrival, output, now)``: a request finished;
* ``measured(recs, w0, w1)``: the requests a run answers for;
* ``max_position(max_len)``: the largest KV position a request can reach.

A kind draws the sizes, the order and the arrival times from the mix's own
``base_seed``, so every run seed serves the same work on the same
schedule: ``--seed`` draws the token ids (and the weights, elsewhere).
Lengths are lognormal (``median``, ``sigma``), rounded and clipped to
``[min, max]``.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Arrival:
    index: int
    due: float
    prompt: np.ndarray
    max_new: int


def load(mix: str) -> dict:
    return json.loads((HERE / "traffic" / f"{mix}.json").read_text())


def lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(rng, rate: float, span: float) -> np.ndarray:
    """``round(rate * span)`` Poisson arrivals whose gaps are scaled to
    end exactly at ``span``; the last gap closes the span."""
    n = max(1, int(round(rate * span)))
    gaps = rng.exponential(1.0 / rate, n + 1)
    return np.cumsum(gaps)[:n] * span / gaps.sum()


def tokens(seed: int, tag: tuple, n: int, vocab: int) -> np.ndarray:
    return np.random.default_rng([seed % 2**63, *tag]).integers(
        0, vocab, n).astype(np.int32)
