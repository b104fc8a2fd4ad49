"""The single-threaded open-loop load generator and its records.

Between engine ticks the generator submits every request whose scheduled time
has passed, then runs one ``engine.step()`` and reads each live request's
``output``: the tokens a streaming client would have received by the end
of that tick.  Each request's records start at its scheduled time, not at
its submission, so a stalled engine delays the clock of every request due
behind it.  When nothing is live or queued the generator sleeps until the
next request is due.

The host work is wrapped in ``jax.profiler.TraceAnnotation`` spans
(``SPANS``), so a traced run can tell what the host did in each of the
device's idle gaps.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

SPANS = ("bench.submit", "bench.step", "bench.observe", "bench.wait")


@dataclasses.dataclass
class Rec:
    arrival: object          # traffic.Arrival
    req: object              # the engine's Request
    due: float
    submitted: float
    admitted: Optional[float] = None  # start of the tick that admitted it
    times: list = dataclasses.field(default_factory=list)  # per token
    done: Optional[float] = None

    @property
    def prompt_len(self) -> int:
        return len(self.arrival.prompt)


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    ctx: list  # attention span (positions 0..pos) of each decoded token


class LoadGen:
    """Drives ``engine`` with ``source`` (``traffic.py``) on ``clock``, a
    function returning seconds since the schedule's time 0."""

    def __init__(self, engine, source, make_request: Callable, *,
                 clock: Callable[[], float], sleep=time.sleep,
                 span=None):
        self.engine, self.source = engine, source
        self.make_request = make_request
        self.clock, self.sleep = clock, sleep
        self.span = span or _no_span
        self.recs: list[Rec] = []
        self.live: list[Rec] = []
        self.ticks: list[Tick] = []
        self.queued = 0  # submitted, not yet admitted

    def _submit(self, now: float) -> None:
        arrivals = self.source.poll(now, self.queued)
        if not arrivals:
            return
        with self.span("bench.submit"):
            for a in arrivals:
                req = self.make_request(a)
                self.engine.submit(req)
                rec = Rec(a, req, due=a.due, submitted=self.clock())
                self.recs.append(rec)
                self.live.append(rec)
                self.queued += 1

    def _observe(self, start: float, end: float) -> None:
        ctx = []
        still = []
        for rec in self.live:
            req = rec.req
            if rec.admitted is None and req.state.name != "QUEUED":
                rec.admitted = start
                self.queued -= 1
            n = len(req.output)
            for k in range(len(rec.times), n):
                rec.times.append(end)
                if k >= 1:  # decode-emitted: attends positions 0..p+k-1
                    ctx.append(rec.prompt_len + k)
            if req.done:
                rec.done = end
                self.source.on_done(rec.arrival, req.output, end)
            else:
                still.append(rec)
        self.live = still
        self.ticks.append(Tick(start, end, ctx))

    def tick(self) -> None:
        start = self.clock()
        with self.span("bench.step"):
            self.engine.step()
        end = self.clock()
        with self.span("bench.observe"):
            self._observe(start, end)

    def run_until(self, t_end: float) -> None:
        """Serve the schedule until ``t_end`` on the clock."""
        while True:
            now = self.clock()
            self._submit(min(now, t_end))
            if now >= t_end:
                return
            if self.live:
                self.tick()
                continue
            nxt = self.source.next_due()
            wait = (t_end if nxt is None else min(nxt, t_end)) - now
            if wait > 0:
                with self.span("bench.wait"):
                    self.sleep(wait)

    def drain(self, recs, since: float, deadline: float) -> None:
        """Tick, submitting nothing, until every one of ``recs`` is done or
        has streamed a token after ``since`` (it is alive, if late), or the
        clock passes ``deadline``."""
        while self.clock() < deadline and any(
                r.done is None and not (r.times and r.times[-1] >= since)
                for r in recs):
            self.tick()


class _no_span:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
